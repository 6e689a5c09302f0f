"""95th percentile over requests of slot time per received token (ms)."""
from bench import window


def read(run):
    return 1000.0 * window.p95(window.tpot_s(run.ticks).values())
