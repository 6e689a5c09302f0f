"""Device time of the prefill programs over device busy time, from the
trace."""
from bench import trace

PROGRAM = "prefill"


def read(run):
    if run.trace is None or not run.trace_window:
        return None
    t0, t1 = run.trace_window
    busy = trace.busy_ns(trace.clip(run.trace["ops"], t0, t1))
    ev = trace.clip(run.trace["modules"], t0, t1)
    ns, n = trace.total_ns(ev, lambda name: PROGRAM in name)
    return ns / busy if busy and n else None
