"""Host milliseconds in the weight-paging layer's spans (booking,
prefetch) in the window (``weight_traffic()['host_s']``) per generated
token."""
from bench import window


def read(run):
    before, after = run.weight
    if "host_s" not in after:
        return None
    n = window.generated_tokens(run.ticks)
    return 1000.0 * (after["host_s"] - before["host_s"]) / n if n else None
