"""Expert-span bytes the programs read from the host store in the window
(``weight_traffic()['read_bytes']``, counted in the fetch branches, every
forward pass and padding entry included) per generated token."""
from bench import window


def read(run):
    before, after = run.weight
    if "read_bytes" not in after:
        return None
    n = window.generated_tokens(run.ticks)
    return (after["read_bytes"] - before["read_bytes"]) / n if n else None
