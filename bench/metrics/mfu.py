"""Model FLOPs of the prompt and generated tokens processed in the window
(counted by the cell's reference module, ``prefill_flops`` and
``decode_flops``) over the window's seconds and the chip's bf16 peak, in
percent."""
from bench import window


def read(run):
    if run.peak is None or not run.model_flops:
        return None
    return 100.0 * run.model_flops / window.window_seconds(run.ticks) \
        / run.peak["bf16_flops_per_s"]
