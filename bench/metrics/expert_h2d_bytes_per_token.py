"""Expert-span bytes the weight-paging layer moved host to device in the
window (``weight_traffic()['expert_bytes']``) per generated token."""
from bench import window


def read(run):
    before, after = run.weight
    if "expert_bytes" not in after:
        return None
    n = window.generated_tokens(run.ticks)
    return (after["expert_bytes"] - before["expert_bytes"]) / n if n else None
