"""Live rows over slot-pool rows, averaged over the window's decode
chunks (engine / scheduler)."""
import math

from bench import window


def read(run):
    v = window.slot_occupancy(run.ticks)
    return None if math.isnan(v) else v
