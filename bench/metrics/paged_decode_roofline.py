"""Share of its roofline that the Pallas paged-decode kernel reaches, in
percent: the least time the bytes and FLOPs of its live work need
(bench/flops.py, summed over every decoded token, layer and row in the
window) over the kernel's device time in the trace."""
from bench import flops, trace

# the Pallas call shows in the trace as "paged_gqa_decode_fused.<n>"
KERNEL = "paged_gqa"


def read(run):
    if run.trace is None or not run.trace_window or run.peak is None:
        return None
    ev = trace.clip(run.trace["ops"], *run.trace_window)
    ns, n = trace.total_ns(ev, lambda name: KERNEL in name)
    if not n or not run.kernel_bytes:
        return None
    least = flops.roofline_seconds(run.kernel_flops, run.kernel_bytes,
                                   run.peak)
    return 100.0 * least / (ns / 1e9)
