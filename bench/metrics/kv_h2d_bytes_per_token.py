"""KV bytes fetched host to device in the window (``kv_traffic()``) per
generated token."""
from bench import window


def read(run):
    before, after = run.kv
    if after.get("mode") != "kv_paged":
        return None
    n = window.generated_tokens(run.ticks)
    return (after["h2d_bytes"] - before["h2d_bytes"]) / n if n else None
