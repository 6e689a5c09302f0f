"""Arithmetic of the measured window, on a log of engine ticks.

Each tick records its host-clock start and end, the requests that held a
slot in it and how many tokens each received.  A request holds a slot in
a tick when it was live before the tick or received a token in it (an
admission prefill always emits one).  All rates are all tokens over all
elapsed time of the window; the window runs from the first tick's start
to the last tick's end.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np


@dataclass
class Tick:
    start: float
    end: float
    tokens: Dict[int, int] = field(default_factory=dict)   # rid -> received
    held: frozenset = frozenset()                          # rids in a slot
    admitted: frozenset = frozenset()                      # rids admitted
    # per rotation group: live rows at its decode chunk, and rows
    live_rows: List[int] = field(default_factory=list)
    pool_rows: int = 0


def window_seconds(ticks: List[Tick]) -> float:
    return ticks[-1].end - ticks[0].start


def generated_tokens(ticks: List[Tick]) -> int:
    return sum(sum(t.tokens.values()) for t in ticks)


def gen_tokens_per_s(ticks: List[Tick]) -> float:
    return generated_tokens(ticks) / window_seconds(ticks)


def tpot_s(ticks: List[Tick]) -> Dict[int, float]:
    """Per request that held a slot in the window: the time it held the
    slot inside the window over the tokens it received there; a request
    that received none counts as the whole window."""
    slot_time: Dict[int, float] = {}
    toks: Dict[int, int] = {}
    for t in ticks:
        for rid in t.held:
            slot_time[rid] = slot_time.get(rid, 0.0) + (t.end - t.start)
            toks[rid] = toks.get(rid, 0) + t.tokens.get(rid, 0)
    whole = window_seconds(ticks)
    return {rid: (slot_time[rid] / toks[rid] if toks[rid] else whole)
            for rid in slot_time}


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(list(values), np.float64), 95))


def slot_occupancy(ticks: List[Tick]) -> float:
    """Live rows over slot-pool rows, averaged over the window's decode
    chunks (one per rotation group that decoded in a tick)."""
    rows = [(live, t.pool_rows) for t in ticks for live in t.live_rows
            if live > 0]
    if not rows:
        return float("nan")
    return float(np.mean([live / pool for live, pool in rows]))
