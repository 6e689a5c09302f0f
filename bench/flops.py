"""Least time on the chip for a piece of work.

The operations and bytes themselves are counted from shapes by each
configuration's reference module (``bench/references/<reference>.py``:
``prefill_flops``, ``decode_flops``, ``decode_kernel_work``), which
knows its architecture's layers and decode kernel.
"""
from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the slower of compute and memory."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
