"""Share of its roofline that the Pallas paged-decode kernel reaches, in
percent: the least time the bytes and FLOPs of its live work need
(counted by the cell's reference module, ``decode_kernel_work``, summed
over every decoded token and row in the window) over the device time of
the kernel the module names (``DECODE_KERNEL``) in the trace."""
from bench import flops, trace


def read(run):
    if run.trace is None or not run.trace_window or run.peak is None:
        return None
    kernel = run.cell.reference.DECODE_KERNEL
    ev = trace.clip(run.trace["ops"], *run.trace_window)
    ns, n = trace.total_ns(ev, lambda name: kernel in name)
    if not n or not run.kernel_bytes:
        return None
    least = flops.roofline_seconds(run.kernel_flops, run.kernel_bytes,
                                   run.peak)
    return 100.0 * least / (ns / 1e9)
