"""Expert residency cache: hypothesis property suite over random
observe/pin/admit/evict traces (occupancy ≤ budget, slot bijection,
pinned spans never evicted, counters sum to total fetches), popularity
EWMA behavior, and the end-to-end transcript-identity guarantee —
greedy outputs bit-identical between whole-layer streaming and
expert-granular paging in hit-heavy and miss-heavy residency regimes on
the mixtral smoke config."""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                          # CI installs it; the bare
    HAS_HYPOTHESIS = False                   # container runs the seeded
                                             # trace test below instead

from repro.core import residency


# ---------------------------------------------------------------------------
# Property suite on the manager itself
# ---------------------------------------------------------------------------

_CAUSES = ("demand", "router", "predicted", "replica")

if HAS_HYPOTHESIS:
    @st.composite
    def _trace(draw):
        L = draw(st.integers(1, 4))
        E = draw(st.integers(1, 8))
        cap = draw(st.integers(0, L * E))
        n_steps = draw(st.integers(1, 12))
        steps = []
        for _ in range(n_steps):
            activated = draw(st.lists(st.booleans(), min_size=L * E,
                                      max_size=L * E))
            hidden = draw(st.lists(st.booleans(), min_size=L * E,
                                   max_size=L * E))
            pin = draw(st.booleans())
            n_admit = draw(st.integers(0, 4))
            admits = [(draw(st.integers(0, L - 1)),
                       draw(st.integers(0, E - 1)),
                       draw(st.sampled_from(_CAUSES)))
                      for _ in range(n_admit)]
            steps.append((activated, hidden, pin, admits))
        return L, E, cap, steps


def _random_trace(rng):
    """Seeded stand-in for the hypothesis strategy (same shape)."""
    L = int(rng.integers(1, 5))
    E = int(rng.integers(1, 9))
    cap = int(rng.integers(0, L * E + 1))
    steps = []
    for _ in range(int(rng.integers(1, 13))):
        activated = rng.random(L * E) < 0.4
        hidden = rng.random(L * E) < 0.3
        pin = bool(rng.integers(0, 2))
        admits = [(int(rng.integers(0, L)), int(rng.integers(0, E)),
                   _CAUSES[int(rng.integers(0, len(_CAUSES)))])
                  for _ in range(int(rng.integers(0, 5)))]
        steps.append((activated.tolist(), hidden.tolist(), pin, admits))
    return L, E, cap, steps


def _check_bijection(r):
    occupied = np.flatnonzero(r.slot_of.reshape(-1) >= 0)
    owners = [o for o in r.owner if o >= 0]
    assert sorted(owners) == sorted(occupied.tolist())
    for pid in owners:
        l, e = divmod(int(pid), r.num_experts)
        assert r.owner[r.slot_of[l, e]] == pid
    assert len(r.free) == r.capacity - len(owners)
    assert sorted(r.free + [int(s) for s in
                            r.slot_of.reshape(-1)[occupied]]) \
        == list(range(r.capacity))


def _run_invariant_trace(trace):
    L, E, cap, steps = trace
    r = residency.ExpertResidency(L, E, capacity=cap, span_bytes=1000)
    total_activated = 0
    for activated, hidden, pin, admits in steps:
        act = np.asarray(activated, bool).reshape(L, E)
        hid = np.asarray(hidden, bool).reshape(L, E)
        total_activated += int(act.sum())
        if pin:
            r.pin_resident()
            pinned_before = {divmod(int(p), E) for p in r.pinned}
        missed = r.observe(act, hidden_mask=hid)
        # missed = exactly the activated non-resident pairs
        assert set(missed) == {(int(l), int(e))
                               for l, e in zip(*np.nonzero(act))
                               if not r.is_resident(l, e)}
        for l, e, cause in admits:
            demand = cause == "demand"
            slot = r.admit(l, e, demand=demand, allow_evict=not demand,
                           cause=None if demand else cause)
            if slot is not None:
                assert r.slot_of[l, e] == slot
        if pin:
            # pinned spans were never evicted while pinned
            for l, e in pinned_before:
                assert r.is_resident(l, e)
            r.unpin_all()
        # replica-pinned spans are never displaced, pin or no pin
        for pid in r.replicas:
            assert r.is_resident(*divmod(int(pid), E))
        assert r.occupancy() <= r.capacity
        _check_bijection(r)
    c = r.counters
    # counters sum to total fetches: every activated expert observation
    # was booked exactly once as a hit or a miss
    assert c.fetches == c.hits + c.misses
    assert c.fetches == total_activated
    # the cause split partitions the hits ...
    assert (c.demand_hits + c.router_hits + c.predicted_hits
            + c.replicated_hits == c.hits)
    # ... and the stall split partitions the misses
    assert 0 <= c.hidden_misses <= c.misses
    assert c.stall_misses == c.misses - c.hidden_misses
    assert int(r.miss_stall_bytes.sum()) == 1000 * c.stall_misses
    # predicted accounting is consistent
    assert 0 <= c.predicted_used <= c.predicted_prefetches
    assert 0.0 <= c.prefetch_accuracy <= 1.0
    assert c.predicted_prefetches + c.replications <= c.prefetches
    # every byte booked is a miss stream or a prefetch transfer
    assert c.h2d_bytes == 1000 * (c.misses + c.prefetches)


if HAS_HYPOTHESIS:
    @given(_trace())
    @settings(max_examples=100, deadline=None)
    def test_residency_invariants(trace):
        _run_invariant_trace(trace)


def test_residency_invariants_seeded():
    """The same invariant checks over seeded random traces, so the bare
    container (no hypothesis) still exercises them in tier-1."""
    for seed in range(25):
        _run_invariant_trace(_random_trace(np.random.default_rng(seed)))


@pytest.mark.parametrize("L,E", [(1, 2), (3, 4), (6, 8)])
def test_pinned_never_evicted_under_pressure(L, E):
    """With every slot pinned, admission of an arbitrarily hot candidate
    must refuse rather than evict (the in-flight chunk may read any
    resident span in place)."""
    r = residency.ExpertResidency(L, E, capacity=1, span_bytes=8)
    assert r.admit(0, 0) is not None
    r.pin_resident()
    act = np.zeros((L, E), bool)
    act[L - 1, E - 1] = True
    for _ in range(5):                      # make the candidate hot
        r.observe(act)
    assert r.admit(L - 1, E - 1) is None
    assert r.is_resident(0, 0)
    r.unpin_all()
    assert r.admit(L - 1, E - 1) is not None     # now evictable
    assert not r.is_resident(0, 0)


def test_victim_quota_lets_demand_misses_converge():
    """PR-3 follow-up: with a reserved victim quota, a demand miss
    (allow_evict=False) may still displace up to `victim_quota` strictly
    colder residents per chunk — a cold cache under a hot steady state
    converges without waiting for the prefetch path.  Quota 0 keeps the
    old refuse-only behavior; the quota refreshes at begin_chunk."""
    def make(quota):
        r = residency.ExpertResidency(1, 4, capacity=1, span_bytes=8,
                                      victim_quota=quota)
        assert r.admit(0, 0) is not None         # pool full of a cold span
        hot = np.zeros((1, 4), bool)
        hot[0, 1] = True
        for _ in range(5):
            r.observe(hot)                       # candidate strictly hotter
        return r

    r0 = make(quota=0)
    assert r0.admit(0, 1, demand=True, allow_evict=False) is None
    assert r0.counters.refusals == 1

    r1 = make(quota=1)
    r1.begin_chunk()
    assert r1.admit(0, 1, demand=True, allow_evict=False) is not None
    assert r1.is_resident(0, 1) and not r1.is_resident(0, 0)
    # quota spent: a second demand eviction this chunk is refused
    cold = np.zeros((1, 4), bool)
    cold[0, 2] = True
    for _ in range(8):
        r1.observe(cold)                         # make (0,2) hottest
    assert r1.admit(0, 2, demand=True, allow_evict=False) is None
    r1.begin_chunk()                             # next chunk: refreshed
    assert r1.admit(0, 2, demand=True, allow_evict=False) is not None


def test_popularity_ewma_prefers_hot_expert():
    r = residency.ExpertResidency(1, 4, capacity=2, span_bytes=8)
    hot = np.array([[True, False, False, False]])
    cold = np.array([[False, True, True, True]])
    for _ in range(8):
        r.observe(hot)
    r.observe(cold)
    assert r.popularity[0, 0] > r.popularity[0, 1]


def test_slots_from_ratio_bounds():
    assert residency.slots_from_ratio(0.0, 4, 8) == 0
    assert residency.slots_from_ratio(1.0, 4, 8) == 32
    assert residency.slots_from_ratio(0.25, 4, 8) == 8
    assert residency.slots_from_ratio(2.0, 4, 8) == 32


# ---------------------------------------------------------------------------
# End-to-end: transcript identity across residency regimes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mixtral_setup():
    import jax
    from repro.configs import get_config
    from repro.models.params import init_params
    cfg = dataclasses.replace(get_config("mixtral-8x7b").smoke(),
                              dtype="float32")
    return cfg, init_params(cfg, jax.random.key(1))


def _serve(cfg, params, work, **kw):
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(cfg, params, EngineConfig(ubatch=2, num_ubs=2, max_seq=64,
                                           page_elems=4096, **kw))
    for p, q in work:
        eng.submit(p, q)
    return eng, eng.run_until_idle()


def test_transcripts_identical_across_residency_regimes(mixtral_setup):
    """Whole-layer streaming, expert-granular hit-heavy (every span fits
    resident), and expert-granular miss-heavy (one slot) must produce
    bit-identical greedy transcripts — residency decides only where bytes
    come from, never what is computed."""
    cfg, params = mixtral_setup
    rng = np.random.default_rng(7)
    work = [(rng.integers(2, cfg.vocab_size, int(rng.integers(2, 20))),
             int(rng.integers(1, 8))) for _ in range(6)]
    _, whole = _serve(cfg, params, work, paged=True)
    hit_eng, hit = _serve(cfg, params, work, expert_paged=True,
                          w_gpu_ratio=1.0)
    miss_eng, miss = _serve(cfg, params, work, expert_paged=True,
                            expert_slots=1)
    assert hit == whole
    assert miss == whole
    # the regimes actually differ as labeled
    th, tm = hit_eng.weight_traffic(), miss_eng.weight_traffic()
    assert th["hit_rate"] > 0.8 > tm["hit_rate"]
    assert th["h2d_bytes"] < tm["h2d_bytes"]


def test_expert_traffic_reduction_vs_whole_layer(mixtral_setup):
    """Acceptance bar: measured H2D weight bytes/token ≥ 2× lower than
    whole-layer streaming on the mixtral smoke config (top-2 of 8) under
    a tight w_gpu_ratio."""
    cfg, params = mixtral_setup
    rng = np.random.default_rng(3)
    work = [(rng.integers(2, cfg.vocab_size, 12), 12) for _ in range(8)]
    base_eng, base = _serve(cfg, params, work, paged=True)
    exp_eng, exp = _serve(cfg, params, work, expert_paged=True,
                          w_gpu_ratio=0.25)
    assert exp == base
    tb, te = base_eng.weight_traffic(), exp_eng.weight_traffic()
    per_tok_base = tb["h2d_bytes"] / max(1, tb["tokens_out"])
    per_tok_exp = te["h2d_bytes"] / max(1, te["tokens_out"])
    assert per_tok_base >= 2.0 * per_tok_exp
    assert te["hits"] + te["misses"] > 0


def test_router_ahead_prefetch_improves_hit_rate(mixtral_setup):
    """The group j+1 lookahead must do observable work: prefetch counters
    advance and the hit rate does not degrade vs. demand-only."""
    cfg, params = mixtral_setup
    rng = np.random.default_rng(5)
    work = [(rng.integers(2, cfg.vocab_size, 12), 16) for _ in range(10)]
    on_eng, on = _serve(cfg, params, work, expert_paged=True,
                        w_gpu_ratio=0.25, prefetch=True)
    off_eng, off = _serve(cfg, params, work, expert_paged=True,
                          w_gpu_ratio=0.25, prefetch=False)
    assert on == off
    t_on, t_off = on_eng.weight_traffic(), off_eng.weight_traffic()
    assert t_on["prefetches"] > 0 == t_off["prefetches"]
    assert t_on["hit_rate"] >= t_off["hit_rate"]


def test_prefetch_drains_through_transfer_plan(mixtral_setup, monkeypatch):
    """The engine's prefetch interleaving is scheduled by
    paging.transfer_plan (satellite decision: wired, not deleted): the
    pending queue must be sliced through it."""
    from repro.core import paging
    cfg, params = mixtral_setup
    calls = []
    orig = paging.transfer_plan

    def spy(pages_per_layer, n_ubs):
        calls.append((pages_per_layer, n_ubs))
        return orig(pages_per_layer, n_ubs)

    monkeypatch.setattr(paging, "transfer_plan", spy)
    rng = np.random.default_rng(5)
    work = [(rng.integers(2, cfg.vocab_size, 12), 16) for _ in range(8)]
    _serve(cfg, params, work, expert_paged=True, w_gpu_ratio=0.25,
           prefetch=True)
    assert calls, "prefetch never consulted transfer_plan"
    assert all(n == 2 for _, n in calls)          # num_ubs slices


# ---------------------------------------------------------------------------
# Expert-span reads counted in the programs; engine spans and program names
# ---------------------------------------------------------------------------

def _recording_engine(cfg, params, **kw):
    """An expert-paged engine whose decode and prefill programs record,
    per call, the token count, the dispatch snapshot's resident map and
    the returned activation counts and reads."""
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(cfg, params, EngineConfig(ubatch=2, num_ubs=2, max_seq=64,
                                           page_elems=4096, expert_paged=True,
                                           **kw))
    calls = []

    def record(fn, window, prefill=False):
        def call(*args):
            (_, rmap), = args[-1].values()
            resident = np.asarray(rmap) >= 0
            # prefill: every position of (1, S) tokens; decode: B rows
            tokens = int(np.prod(args[1].shape)) if prefill \
                else int(args[2].shape[0])
            out = fn(*args)
            counts, reads = out[-2:]
            (c,), (r,) = counts.values(), reads.values()
            calls.append((tokens, resident, np.asarray(c), np.asarray(r),
                          window))
            return out
        return call

    eng._prefill = record(eng._prefill, False, prefill=True)
    eng._decode_chunk = record(eng._decode_chunk, False)
    if eng._decode_window_fn is not None:
        eng._decode_window_fn = record(eng._decode_window_fn, True)
    return eng, calls


def _expected_reads(resident, counts, window):
    """Reads per (pass, layer) from the routing alone: every activated
    expert is read once, from the host store where the snapshot lacks
    its span and from the pool where it holds it; the padding entries of
    the activated set (``moe.activated_experts`` pads ``sel`` with 0)
    read nothing."""
    act = counts > 0
    if window:
        act = act.any(axis=-2)
    act = act.reshape(-1, *resident.shape)             # (passes, L, E)
    return (act & ~resident).sum(-1), act.sum(-1)


@pytest.mark.parametrize("module_batch", [False, True],
                         ids=["lockstep", "window"])
def test_program_counts_every_host_read(mixtral_setup, module_batch):
    """With a chunk of 8 and a mostly empty pool, the reads the programs
    count equal, per pass and layer, an independent count from the
    dispatch snapshot and the routing: host reads are the activated
    non-resident experts, host + pool reads the activated experts, and
    padding entries read nothing.  The engine's totals are their sums,
    and the bytes the programs read exceed what the host books."""
    cfg, params = mixtral_setup
    eng, calls = _recording_engine(cfg, params, expert_slots=2,
                                   decode_chunk=8, module_batch=module_batch)
    rng = np.random.default_rng(11)
    for _ in range(5):
        eng.submit(rng.integers(2, cfg.vocab_size, int(rng.integers(4, 20))),
                   12)
    eng.run_until_idle()
    host = pool = padded = 0
    for tokens, resident, counts, reads, window in calls:
        asked, activated = _expected_reads(resident, counts, window)
        got = reads.reshape(-1, *reads.shape[-2:])       # (passes, L, 2)
        A = min(cfg.num_experts, tokens * cfg.top_k)
        np.testing.assert_array_equal(got[..., 0], asked)
        np.testing.assert_array_equal(got[..., 0] + got[..., 1], activated)
        host += int(got[..., 0].sum())
        pool += int(got[..., 1].sum())
        padded += int((A - activated).sum())
    assert any(w for *_, w in calls) == module_batch
    t = eng.weight_traffic()
    span = eng.residency["p0"].span_bytes
    assert (t["read_spans"], t["pool_reads"]) == (host, pool)
    # the activated sets had padding, and none of it was read
    assert padded > 0 and t["pad_reads"] == 0
    assert pool > 0
    assert t["read_bytes"] == host * span
    assert t["read_bytes"] > t["expert_bytes"] > 0


# ---------------------------------------------------------------------------
# The per-entry fetch: each activated expert applied from its span
# ---------------------------------------------------------------------------

def _paged_moe_case(expert_dtype, seed=3):
    """A bf16 mixtral smoke layer, its packed expert store, and the moe
    params of layer 1."""
    import jax
    from repro.configs import get_config
    from repro.core import paging
    from repro.models.params import init_params
    cfg = dataclasses.replace(get_config("mixtral-8x7b").smoke(),
                              dtype="bfloat16", expert_dtype=expert_dtype)
    params = init_params(cfg, jax.random.key(seed))
    pw = paging.pack_block_groups_split(params["blocks"], 4096)
    p = jax.tree.map(lambda a: a[1], params["blocks"]["p0"]["moe"])
    return cfg, p, pw.expert_pages["p0"], pw.expert_manifests["p0"]


def _expert_ctx(store, em, resident):
    """An _ExpertCtx whose pool holds layer 1's ``resident`` experts."""
    import jax.numpy as jnp
    from repro.models.model import _ExpertCtx
    rmap = np.full((em.num_layers, em.num_experts), -1, np.int32)
    rmap[1, resident] = np.arange(len(resident))
    pool = jnp.asarray(np.asarray(store)[1, resident])
    return _ExpertCtx(jnp.asarray(store), em, pool, jnp.asarray(rmap))


@pytest.mark.parametrize("token_groups", [None, 2], ids=["lockstep", "window"])
@pytest.mark.parametrize("expert_dtype", ["", "int8"], ids=["bf16", "int8"])
def test_paged_moe_matches_dense_bit_for_bit(expert_dtype, token_groups):
    """For random routings whose activated set is shorter than the fetch
    (padding entries), with experts read from the pool and from the host
    store, moe_paged applying each expert straight from its span equals
    moe_dense on the full expert set bit-for-bit, and reads each
    activated expert once."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    cfg, p, store, em = _paged_moe_case(expert_dtype)
    rng = np.random.default_rng(5)
    seen = {"pad": 0, "pool": 0, "host": 0}
    for trial in range(6):
        T = (2, 4)[trial % 2] * (token_groups or 1)
        resident = rng.choice(cfg.num_experts, 3, replace=False)
        ctx = _expert_ctx(store, em, resident)
        x = jnp.asarray(rng.normal(0, 1, (T, cfg.d_model)), jnp.bfloat16)

        @jax.jit
        def step(x):
            return moe.moe_paged(cfg, p, x, fetch_experts=ctx.make_fetch(
                jnp.int32(1)), token_groups=token_groups)

        out, _, counts, reads = step(x)
        ref, _ = jax.jit(lambda x: moe.moe_dense(cfg, p, x))(x)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(ref, np.float32))
        act = np.asarray(counts).reshape(-1, cfg.num_experts).sum(0) > 0
        in_pool = np.isin(np.arange(cfg.num_experts), resident)
        np.testing.assert_array_equal(
            np.asarray(reads), [(act & ~in_pool).sum(), (act & in_pool).sum()])
        seen["pad"] += min(cfg.num_experts, T * cfg.top_k) - int(act.sum())
        seen["pool"] += int(reads[1])
        seen["host"] += int(reads[0])
    assert all(seen.values()), seen


def test_paged_moe_stacks_no_expert_subset():
    """The lowered program of one paged MoE step holds one switch per
    entry of the activated set and no concatenate as large as the
    activated experts' weights (the fetch used to stack the spans)."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.models import moe
    cfg, p, store, em = _paged_moe_case("")
    ctx = _expert_ctx(store, em, np.array([0, 5]))
    T = 4
    A = min(cfg.num_experts, T * cfg.top_k)
    x = jnp.zeros((T, cfg.d_model), jnp.bfloat16)
    text = jax.jit(lambda x: moe.moe_paged(
        cfg, p, x, fetch_experts=ctx.make_fetch(jnp.int32(1)))).lower(
        x).as_text()
    assert text.count("stablehlo.case") == A
    span = int(np.prod(em.span_shape))
    for dims in re.findall(r"stablehlo\.concatenate.*-> tensor<([\dx]+)x",
                           text):
        assert int(np.prod([int(d) for d in dims.split("x")])) < span, dims


def _span_events(trace_dir):
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("repro.")]


# each span with the spans it must sit inside (any of them)
_NESTING = {
    "repro.engine.step": (),
    "repro.sched.admit": ("repro.engine.step",),
    "repro.engine.prefill": ("repro.engine.step",),
    "repro.engine.decode": ("repro.engine.step",),
    "repro.engine.dispatch": ("repro.engine.decode", "repro.engine.prefill"),
    "repro.engine.wait": ("repro.engine.decode", "repro.engine.prefill"),
    "repro.weights.book": ("repro.engine.decode", "repro.engine.prefill"),
    "repro.weights.prefetch": ("repro.engine.decode",),
    "repro.weights.copy": ("repro.weights.book", "repro.weights.prefetch"),
    "repro.kv.prepare": ("repro.engine.step",),
    "repro.kv.prefetch": ("repro.engine.step",),
    "repro.kv.spill": ("repro.kv.prepare", "repro.kv.prefetch"),
    "repro.kv.fetch": ("repro.kv.prepare", "repro.kv.prefetch"),
}


def test_step_trace_holds_nested_spans(mixtral_setup, tmp_path):
    """A profiler trace of one Engine.step (expert-paged weights, paged
    KV that spills) holds every engine span, each inside its parent;
    the layers' host seconds and the KV programs are counted."""
    import jax
    from repro.serving.engine import Engine, EngineConfig
    cfg, params = mixtral_setup
    eng = Engine(cfg, params, EngineConfig(
        ubatch=2, num_ubs=2, max_seq=64, page_elems=4096, expert_paged=True,
        w_gpu_ratio=0.125, kv_paged=True, kv_gpu_ratio=0.25))
    rng = np.random.default_rng(0)
    for _ in range(6):
        eng.submit(rng.integers(2, cfg.vocab_size, int(rng.integers(8, 30))),
                   20)
    eng.step()
    eng.step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    ev = _span_events(tmp_path)
    assert {n for n, *_ in ev} == set(_NESTING)
    assert sum(n == "repro.engine.step" for n, *_ in ev) == 1
    for name, s, e in ev:
        parents = _NESTING[name]
        assert not parents or any(
            p == pn and ps <= s and e <= pe
            for p in parents for pn, ps, pe in ev), name
    assert eng.weight_traffic()["host_s"] > 0
    kv = eng.kv_traffic()
    assert kv["host_s"] > 0 and kv["dispatches"] > 0


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["monolithic", "staged"])
def test_program_names_match_the_trace_readers(mixtral_setup, overlap):
    """Every program the engine compiles has a name: the decode programs
    and only they contain ``decode_chunk``, the prefill programs and
    only they contain ``prefill``, and none is a lambda."""
    import jax
    cfg, params = mixtral_setup
    names = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(str(kw.get("fun_name")))

    jax.clear_caches()          # compile every program this engine uses
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        eng, _ = _serve(cfg, params, [(np.arange(2, 30), 12)] * 4,
                        expert_paged=True, w_gpu_ratio=0.125, kv_paged=True,
                        kv_gpu_ratio=0.25, overlap=overlap,
                        module_batch=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert eng.kv_traffic()["spills"] > 0
    progs = {n[len("jit("):-1] for n in names}         # "jit(<name>)"
    assert {n for n in progs if "decode_chunk" in n} == {"decode_chunk"}
    assert {n for n in progs if "prefill" in n} == {
        "prefill_chunk" if overlap else "prefill_step"}
    assert not [n for n in progs if "lambda" in n]
    assert {"kv_spill_read", "kv_fetch_write", "kv_clear",
            "pool_write"} <= progs
