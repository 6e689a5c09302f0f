"""One benchmark run of a serving cell: build, warm up, measure, check.

What belongs to the architecture comes from the cell's reference module
(``cell.reference``, bench/references/<reference>.py): the sizes, the
engine's parameter tree drawn from the seed, and the model and
decode-kernel work each admitted prompt and generated token costs.  The
engine is the program's ``repro.serving.engine.Engine`` with the
cell's ``EngineConfig`` fields (every other field at its default) and
``ExecPolicy``.  The harness drives it through ``submit`` and ``step``
as a closed loop of offline batches: before every tick it tops the
scheduler's queue up to one slot pool of requests, so no slot waits for
traffic.  Set-up draws the weights, builds the engine, compiles every
prefill width the traffic can ask for, and runs ticks until every slot
has been filled once, KV blocks have been spilled and fetched back, and
a tick compiled nothing.  The window then starts on a tick boundary and
ends on the first tick boundary after ``seconds``.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from bench import spec, window
from bench.traffic import Traffic

MAX_WARM_TICKS = 16
QUIET_TICKS = 2           # consecutive warm-up ticks that compile nothing


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts jaxpr traces, backend compiles and persistent-cache loads."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.n = {"traces": 0, "compiles": 0, "cache_hits": 0}
        self.names = []

        def on_duration(event, secs, **kw):
            key = self.EVENTS.get(event)
            if key:
                self.n[key] += 1
                if key == "compiles":
                    self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def total(self) -> int:
        return sum(self.n.values())


@dataclass
class RunRecord:
    """What the metric readers see of one run."""
    cell: object
    dims: dict
    ticks: List[window.Tick]
    setup_s: float
    weight: tuple                 # weight_traffic() before, after window
    kv: tuple                     # kv_traffic() before, after window
    model_flops: float
    kernel_flops: float
    kernel_bytes: float
    peak: dict
    trace: Optional[dict] = None
    trace_window: Optional[tuple] = None


def _params(ref, cfg, dims, seed):
    """The reference module's engine tree, checked leaf by leaf against
    the layout the engine expects."""
    import jax

    from repro.models.params import abstract_params

    params = ref.engine_params(cfg, dims, seed)
    want = abstract_params(cfg)
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    need = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), want)
    if have != need:
        raise spec.SpecError(f"weights do not fit the engine's layout: "
                             f"{have} vs {need}")
    return params


class Loop:
    """Closed-loop runner of one engine: ``tick()`` tops up the queue,
    runs one ``Engine.step`` and logs it."""

    def __init__(self, eng, traffic: Traffic, ref, dims: dict):
        self.eng = eng
        self.traffic = traffic
        self.ref = ref
        self.dims = dims
        self.sched = eng.scheduler
        self.ubatch = len(self.sched.slots[0])
        self.pool_rows = len(self.sched.slots) * self.ubatch
        self.depth = traffic.queue_pools * self.pool_rows
        self.prompt_len: Dict[int, int] = {}
        self.submitted_at: Dict[int, int] = {}
        self.finished_at: Dict[int, int] = {}
        self.n_ticks = 0
        self.model_flops = 0.0
        self.kernel_flops = 0.0
        self.kernel_bytes = 0.0
        self.filled = set()           # (gid, row) slots that held a request

    def _live(self) -> Dict[int, int]:
        return {s.req.rid: s.gid for grp in self.sched.slots for s in grp
                if s.req is not None}

    def tick(self) -> window.Tick:
        import jax

        eng, sched = self.eng, self.sched
        with jax.profiler.TraceAnnotation("bench.submit"):
            while len(sched.queue) < self.depth:
                prompt, n = next(self.traffic)
                rid = eng.submit(prompt, n)
                self.prompt_len[rid] = len(prompt)
                self.submitted_at[rid] = self.n_ticks
        live = self._live()
        queued = [r.rid for r in sched.queue]
        before = {rid: len(sched.requests[rid].generated)
                  for rid in live}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        t1 = time.perf_counter()
        gid_of = dict(live)
        for grp in sched.slots:
            for s in grp:
                if s.req is not None:
                    self.filled.add((s.gid, s.row))
                for rid in s.history[-4:]:
                    gid_of.setdefault(rid, s.gid)
        admitted = {rid for rid in queued
                    if sched.requests[rid].generated}
        tokens, live_rows = {}, [0] * len(sched.slots)
        ref, dims = self.ref, self.dims
        for rid in set(live) | admitted:
            r = sched.requests[rid]
            g0 = before.get(rid, 0)
            got = len(r.generated) - g0
            tokens[rid] = got
            n_dec = got - (1 if rid in admitted else 0)
            start = g0 + (1 if rid in admitted else 0)
            p = self.prompt_len[rid]
            if rid in admitted:
                self.model_flops += ref.prefill_flops(dims, p)
            if n_dec > 0:
                live_rows[gid_of[rid]] += 1
            for k in range(n_dec):
                ctx = p + start + k
                self.model_flops += ref.decode_flops(dims, ctx)
                f, b = ref.decode_kernel_work(dims, ctx)
                self.kernel_flops += f
                self.kernel_bytes += b
            if r.done:
                self.finished_at.setdefault(rid, self.n_ticks)
        self.n_ticks += 1
        return window.Tick(t0, t1, tokens, frozenset(set(live) | admitted),
                           frozenset(admitted), live_rows, self.ubatch)

    def reset_work(self):
        self.model_flops = self.kernel_flops = self.kernel_bytes = 0.0


def _warm_prefill(eng, widths) -> int:
    """Compile and run the admission prefill at every width the traffic
    can ask for, against an expert map that reads every span from the
    device pool, so warming streams no weights.  Returns widths warmed."""
    import jax
    import jax.numpy as jnp

    try:
        state = {k: (pool, jnp.zeros(eng.residency[k].slot_of.shape,
                                     jnp.int32))
                 for k, pool in eng._expert_pool.items()}
        fn, scratch, params = eng._prefill, eng._prefill_scratch, eng.params
    except AttributeError as e:       # the engine's internals moved
        log(f"prefill warm-up skipped: {e!r}")
        return 0
    for S in widths:
        toks = jnp.zeros((1, S), jnp.int32)
        lens = jnp.asarray([S], jnp.int32)
        out = (fn(params, toks, scratch, lens, state) if state
               else fn(params, toks, scratch, lens))
        jax.block_until_ready(out)
    return len(widths)


def _placement(eng) -> Dict[str, object]:
    pw = eng.paged_blocks
    weight_kinds = sorted({a.sharding.memory_kind for a in
                           [*pw.pages.values(), *pw.expert_pages.values()]})
    kv_kinds = sorted({getattr(getattr(a, "sharding", None), "memory_kind",
                               "numpy")
                       for g in eng._kv_host.values() for a in g.values()})
    return {"weight_store": weight_kinds, "kv_host_tier": kv_kinds}


def _sample(requests: dict, seed: int, min_tokens: int, max_requests: int):
    """Requests to compare: the longest finished one, then others drawn
    from the seed until ``min_tokens`` served tokens are in."""
    if not requests:
        return []
    rids = sorted(requests, key=lambda r: (-len(requests[r].generated), r))
    chosen = [rids[0]]
    rest = list(np.random.default_rng(seed).permutation(rids[1:]))
    total = len(requests[rids[0]].generated)
    while rest and total < min_tokens and len(chosen) < max_requests:
        rid = int(rest.pop(0))
        chosen.append(rid)
        total += len(requests[rid].generated)
    return chosen


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, impl: Optional[str] = None, trace_dir: Optional[str] = None,
             mutate=None) -> dict:
    """Returns the result dict (without printing).  ``impl`` overrides the
    cell's paged-attention implementation (the CPU tests use the Pallas
    interpreter); ``mutate(eng)`` lets a test break the timed path."""
    import jax

    from repro.models.model import ExecPolicy
    from repro.serving.engine import Engine, EngineConfig

    cfg = spec.model_config(cell.config)
    ref = cell.reference
    dims = ref.dims(cfg)
    st = cell.settings
    dev = jax.devices()[0]
    peaks = spec.load_peaks()
    peak = peaks.get(dev.device_kind) if dev.platform != "cpu" else None
    if dev.platform != "cpu" and peak is None:
        raise spec.SpecError(f"device kind {dev.device_kind!r} is not in "
                             "bench/peaks.json")
    counter = CompileCounter()

    t0 = time.perf_counter()
    params = _params(ref, cfg, dims, seed)
    t_weights = time.perf_counter() - t0
    pol = dict(st.get("policy", {}))
    if impl is not None:
        pol["paged_attn_impl"] = impl
    t0 = time.perf_counter()
    eng = Engine(cfg, params, EngineConfig(**st["engine"]),
                 ExecPolicy(**pol))
    del params
    gc.collect()
    t_engine = time.perf_counter() - t0
    placement = _placement(eng)
    log(f"weights {t_weights:.2f} s, engine init (host packing) "
        f"{t_engine:.2f} s, placement {placement}")
    if dev.platform == "tpu" and (placement["weight_store"] != ["pinned_host"]
                                  or placement["kv_host_tier"]
                                  != ["pinned_host"]):
        raise spec.SpecError(f"host tiers not in pinned_host: {placement}")
    if mutate is not None:
        mutate(eng)

    traffic = Traffic(cell.traffic, seed, cfg.vocab_size)
    loop = Loop(eng, traffic, ref, dims)
    kv_paged = st["engine"].get("kv_paged", False)
    pool_slots = loop.pool_rows
    quiet_run = 0
    for i in range(MAX_WARM_TICKS):
        c0 = counter.total()
        tk = loop.tick()
        new = counter.total() - c0
        if i == 0:
            # after the first tick the expert pool is a program's output
            # (committed to the device), as it is in every later prefill
            t0 = time.perf_counter()
            nw = _warm_prefill(eng, traffic.prompt_buckets(
                16, st["engine"]["max_seq"]))
            log(f"prefill warm-up: {nw} widths in "
                f"{time.perf_counter() - t0:.2f} s")
        kt = eng.kv_traffic()
        kv_ok = (not kv_paged) or (kt.get("spills", 0) > 0 and
                                   kt.get("misses", 0)
                                   + kt.get("prefetches", 0) > 0)
        quiet_run = quiet_run + 1 if new == 0 else 0
        log(f"warm tick {i}: {tk.end - tk.start:.3f} s, "
            f"{sum(tk.tokens.values())} tokens, new programs {new}, "
            f"slots filled {len(loop.filled)}/{pool_slots}, kv spills "
            f"{kt.get('spills', 0)}")
        if len(loop.filled) == pool_slots and kv_ok and \
                quiet_run >= QUIET_TICKS:
            break
    loop.reset_work()

    # ----------------------------------------------------------- window
    w_before, kv_before = eng.weight_traffic(), eng.kv_traffic()
    c_before = dict(counter.n)
    n_names = len(counter.names)
    tdir = None
    if trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    ticks: List[window.Tick] = []
    first_tick = loop.n_ticks
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            ticks.append(loop.tick())
            if ticks[-1].end - t_w0 >= seconds:
                break
    if trace:
        jax.profiler.stop_trace()
    w_after, kv_after = eng.weight_traffic(), eng.kv_traffic()
    in_window = {k: counter.n[k] - c_before[k] for k in counter.n}
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {len(ticks)} ticks, {window.window_seconds(ticks):.3f} s, "
        f"tick mean {np.mean([t.end - t.start for t in ticks]):.3f} s; "
        f"programs traced/compiled/loaded in the window {in_window} "
        f"{counter.names[n_names:]}")

    rec = RunRecord(cell, dims, ticks, setup_s, (w_before, w_after),
                    (kv_before, kv_after), loop.model_flops,
                    loop.kernel_flops, loop.kernel_bytes, peak)
    if trace:
        from bench import trace as trace_mod
        tr = trace_mod.load(tdir)
        rec.trace = tr
        rec.trace_window = trace_mod.window(tr)
        if trace_dir:
            import gzip
            import os
            os.makedirs(trace_dir, exist_ok=True)
            import json
            with gzip.open(f"{trace_dir}/reduced.json.gz", "wt") as f:
                json.dump(tr, f)
            with open(f"{trace_dir}/planes.txt", "w") as f:
                f.write("\n".join(trace_mod.describe(tdir)))
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)

    # ------------------------------------------------- outcome of requests
    sched = eng.scheduler
    held = set().union(*(t.held for t in ticks))
    win_ticks = range(first_tick, loop.n_ticks)
    failed = 0
    for rid in held:
        r = sched.requests[rid]
        bad_tok = any(not 0 <= t < cfg.vocab_size for t in r.generated)
        if r.aborted or bad_tok:
            failed += 1
        elif r.done and not (len(r.generated) == r.max_new_tokens or (
                r.generated and r.generated[-1] == eng.ecfg.eos_id)):
            failed += 1
    failed += sum(1 for rid, r in sched.requests.items()
                  if r.aborted and rid not in held
                  and loop.submitted_at.get(rid) in win_ticks)
    finished = {rid: sched.requests[rid] for rid, k in loop.finished_at.items()
                if k in win_ticks and not sched.requests[rid].aborted}
    chk = st["check"]
    chosen = _sample(finished, seed, chk["sample_tokens"],
                     chk["max_requests"])
    seqs = [(np.asarray(sched.requests[rid].prompt),
             list(sched.requests[rid].generated)) for rid in chosen]
    del eng, loop, sched
    gc.collect()

    out = {"attempted": len(held), "failed": failed, "record": rec,
           "memory_peak_bytes": mem_peak, "in_window": in_window,
           "device": dev, "seqs": seqs, "dims": dims}
    return out
