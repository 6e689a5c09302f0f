"""Host milliseconds in the KV-paging layer's spans (block preparation,
prefetch) in the window (``kv_traffic()['host_s']``) per generated
token."""
from bench import window


def read(run):
    before, after = run.kv
    if "host_s" not in after:
        return None
    n = window.generated_tokens(run.ticks)
    return 1000.0 * (after["host_s"] - before["host_s"]) / n if n else None
