"""Reduction of a profiler trace to device busy time, program and kernel
times, and idle gaps attributed to the harness's host spans.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact dict of events (names, start and duration in nanoseconds, on the
profiler's common clock):

  ops      device ops of the first chip ("XLA Ops" line)
  modules  compiled programs run on the first chip ("XLA Modules" line)
  host     the harness's host spans (``TraceAnnotation`` names "bench.*")

Everything else works on that dict, so a small recorded trace checks the
reduction without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

HOST_PREFIX = "bench."


def _device_plane(planes):
    devs = sorted((p for p in planes if p.name.startswith("/device:")
                   and "TPU" in p.name.upper()), key=lambda p: p.name)
    return devs[0] if devs else None


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = list(pd.planes)
    out = {"ops": [], "modules": [], "host": []}
    dev = _device_plane(planes)
    if dev is not None:
        for line in dev.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:
                out[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX)]
    for k in out:
        out[k].sort(key=lambda e: e[1])
    return out


def describe(trace_dir: str, limit: int = 12) -> List[str]:
    """Planes, lines and sample event names of a raw trace (for reading
    one by hand)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    rows = []
    for p in ProfileData.from_file(path).planes:
        for line in p.lines:
            ev = list(line.events)
            t0 = min((e.start_ns for e in ev), default=0)
            t1 = max((e.start_ns + e.duration_ns for e in ev), default=0)
            names = sorted({e.name for e in ev})
            rows.append(f"{p.name} | {line.name} | {len(ev)} events "
                        f"[{t0}, {t1}] | {names[:limit]}")
    return rows


def clip(events, t0: int, t1: int) -> List[Tuple[str, int, int]]:
    """Events cut to the window [t0, t1)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by the events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[int]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events) -> int:
    return sum(b - a for a, b in union(events))


def total_ns(events, match: Callable[[str], bool]) -> Tuple[int, int]:
    """(summed duration, count) of the events whose name matches."""
    sel = [d for n, _, d in events if match(n)]
    return sum(sel), len(sel)


def window(tr: dict) -> Optional[Tuple[int, int]]:
    """The measured window: the harness's "bench.window" host span."""
    for name, s, d in tr["host"]:
        if name == HOST_PREFIX + "window":
            return s, s + d
    return None


def gaps(events, t0: int, t1: int) -> List[Tuple[int, int]]:
    """Idle [start, end) intervals of the device inside [t0, t1)."""
    out, cur = [], t0
    for a, b in union(clip(events, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return out


def host_span_at(tr: dict, t: int) -> str:
    """The innermost harness span that covers host time ``t``."""
    best = None
    for name, s, d in tr["host"]:
        if s <= t < s + d and name != HOST_PREFIX + "window" and (
                best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any harness span"


def op_name(name: str) -> str:
    """An HLO op event's name without its operand text."""
    return name.split(" = ", 1)[0]


def self_times(events) -> List[Tuple[str, int]]:
    """(name, self time) per event: its duration less the time covered by
    the events nested inside it (a while or cond op holds its body's ops
    on the same line)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: List[List] = []          # [name, end, child_covered]

    def close(top):
        name, end, start, covered = top
        out.append((name, end - start - covered))

    for name, s, d in evs:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += d
        stack.append([name, s + d, s, 0])
    while stack:
        close(stack.pop())
    return out


def breakdown(tr: dict, t0: int, t1: int, n: int = 10) -> dict:
    """Top device ops by self time, and the longest idle gaps named by what
    the host was doing at their midpoint (seconds)."""
    by_op: Dict[str, int] = {}
    for name, d in self_times(clip(tr["ops"], t0, t1)):
        name = op_name(name)
        by_op[name] = by_op.get(name, 0) + d
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(gaps(tr["ops"], t0, t1), key=lambda g: g[0] - g[1])[:n]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[host_span_at(tr, (a + b) // 2), (b - a) / 1e9]
                          for a, b in idle]}
