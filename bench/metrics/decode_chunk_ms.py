"""Device time of the decode-chunk program per call, from the trace."""
from bench import trace

PROGRAM = "decode_chunk"


def read(run):
    if run.trace is None or not run.trace_window:
        return None
    ev = trace.clip(run.trace["modules"], *run.trace_window)
    ns, n = trace.total_ns(ev, lambda name: PROGRAM in name)
    return ns / n / 1e6 if n else None
