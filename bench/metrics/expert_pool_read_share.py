"""Expert-span reads served by the device pool over all expert-span reads
the programs made in the window (``weight_traffic()`` ``pool_reads`` and
``read_spans``, counted in the fetch branches)."""


def read(run):
    before, after = run.weight
    if "pool_reads" not in after or "read_spans" not in after:
        return None
    pool = after["pool_reads"] - before["pool_reads"]
    host = after["read_spans"] - before["read_spans"]
    return pool / (pool + host) if pool + host else None
