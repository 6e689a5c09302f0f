"""Expert-span hits over hits and misses in the window
(``weight_traffic()``)."""


def read(run):
    before, after = run.weight
    if "hits" not in after:
        return None
    hits = after["hits"] - before["hits"]
    misses = after["misses"] - before["misses"]
    return hits / (hits + misses) if hits + misses else None
