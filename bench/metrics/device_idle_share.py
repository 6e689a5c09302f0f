"""1 - (union of device-op intervals over the window), from the trace."""
from bench import trace


def read(run):
    if run.trace is None or not run.trace_window:
        return None
    t0, t1 = run.trace_window
    busy = trace.busy_ns(trace.clip(run.trace["ops"], t0, t1))
    return 1.0 - busy / (t1 - t0) if busy else None
