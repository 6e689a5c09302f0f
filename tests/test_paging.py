"""Paged weights: pack/fetch roundtrip (property-based), page table math,
transfer plan coverage, in-scan span reconstruction, paged forward equals
resident forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                  # CI installs it; the deterministic
    HAS_HYPOTHESIS = False           # tests below still run bare

from repro.core import paging


def _tree(rng, L, shapes):
    return {f"w{i}": jnp.asarray(rng.normal(0, 1, (L,) + s), jnp.float32)
            for i, s in enumerate(shapes)}


def _check_pack_fetch_roundtrip(L, shapes, page_elems):
    rng = np.random.default_rng(L * 1000 + page_elems)
    tree = _tree(rng, L, shapes)
    pages, manifest = paging.pack_layer_stack(tree, page_elems)
    assert pages.shape == (L * manifest.pages_per_layer, page_elems)
    for layer in range(L):
        got = paging.fetch_layer(pages, manifest, layer)
        for k in tree:
            np.testing.assert_array_equal(got[k], tree[k][layer])


if HAS_HYPOTHESIS:
    @given(st.integers(1, 5), st.integers(1, 4),
           st.lists(st.tuples(st.integers(1, 7), st.integers(1, 9)),
                    min_size=1, max_size=4),
           st.sampled_from([16, 64, 257]))
    @settings(max_examples=40, deadline=None)
    def test_pack_fetch_roundtrip(L, _unused, shapes, page_elems):
        _check_pack_fetch_roundtrip(L, shapes, page_elems)


def test_pack_fetch_roundtrip_seeded():
    for seed in range(8):
        r = np.random.default_rng(seed)
        shapes = [tuple(r.integers(1, 8, 2)) for _ in range(r.integers(1, 5))]
        _check_pack_fetch_roundtrip(int(r.integers(1, 6)), shapes,
                                    int(r.choice([16, 64, 257])))


def test_unflatten_span_equals_fetch_layer(rng):
    tree = _tree(rng, 3, [(4, 5), (2,), (3, 3)])
    pages, manifest = paging.pack_layer_stack(tree, 32)
    span = pages.reshape(3, manifest.pages_per_layer, 32)[1]
    a = paging.unflatten_span(span, manifest)
    b = paging.fetch_layer(pages, manifest, 1)
    for k in tree:
        np.testing.assert_array_equal(a[k], b[k])


def _check_transfer_plan(pages_per_layer, n_ubs):
    plan = paging.transfer_plan(pages_per_layer, n_ubs)
    flat = [p for g in plan for p in g]
    assert flat == list(range(pages_per_layer))
    assert len(plan) == n_ubs
    sizes = [len(g) for g in plan]
    assert max(sizes) - min(sizes) <= 1          # balanced interleave


if HAS_HYPOTHESIS:
    @given(st.integers(1, 64), st.integers(1, 16))
    @settings(max_examples=50, deadline=None)
    def test_transfer_plan_partitions_pages(pages_per_layer, n_ubs):
        _check_transfer_plan(pages_per_layer, n_ubs)


def test_transfer_plan_partitions_pages_seeded():
    for ppl, n in [(1, 1), (5, 2), (64, 16), (7, 9), (16, 4)]:
        _check_transfer_plan(ppl, n)


def test_double_buffer_semantics():
    db = paging.DoubleBuffer()
    s0 = db.load(0)
    s1 = db.load(1)
    assert s0 != s1
    assert db.is_resident(0) and db.is_resident(1)
    db.load(2)                                    # evicts layer 0
    assert db.is_resident(2) and not db.is_resident(0)


def test_paged_forward_matches_resident(rng):
    from repro.configs import get_config
    from repro.models import forward, unembed
    from repro.models.params import init_params
    cfg = dataclasses.replace(get_config("qwen2.5-3b").smoke(),
                              dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (2, 16)), jnp.int32)
    ref = unembed(cfg, params, forward(cfg, params, toks)["hidden"])
    paged = paging.pack_block_groups(params["blocks"], page_elems=1 << 12)
    got = unembed(cfg, params,
                  forward(cfg, params, toks, paged_blocks=paged)["hidden"])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Split manifests (shared span + per-(layer, expert) spans)
# ---------------------------------------------------------------------------

def _moe_group(rng, L=3, E=4, D=6, F=10):
    return {
        "attn": {"wq": jnp.asarray(rng.normal(0, 1, (L, D, D)), jnp.float32)},
        "attn_norm": {"scale": jnp.asarray(rng.normal(0, 1, (L, D)),
                                           jnp.float32)},
        "moe": {
            "router": jnp.asarray(rng.normal(0, 1, (L, D, E)), jnp.float32),
            "wi": jnp.asarray(rng.normal(0, 1, (L, E, D, 2, F)), jnp.float32),
            "wo": jnp.asarray(rng.normal(0, 1, (L, E, F, D)), jnp.float32),
        },
    }


@pytest.mark.parametrize("page_elems", [16, 64, 257])
def test_split_pack_roundtrip(rng, page_elems):
    """Shared span excludes expert leaves; expert spans rebuild each
    (layer, expert) slice exactly; the page-id table is dense & disjoint."""
    tree = _moe_group(rng)
    shared, experts, sm = paging.pack_layer_stack_split(tree, page_elems)
    L, E = 3, 4
    # shared manifest holds everything except the routed expert leaves
    shared_paths = {e.path for e in sm.shared.leaves}
    assert ("moe", "router") in shared_paths
    assert ("moe", "wi") not in shared_paths
    for layer in range(L):
        got = paging.fetch_layer(shared, sm.shared, layer)
        np.testing.assert_array_equal(got["attn"]["wq"],
                                      tree["attn"]["wq"][layer])
        np.testing.assert_array_equal(got["moe"]["router"],
                                      tree["moe"]["router"][layer])
        assert "wi" not in got["moe"]
    # expert spans: exact per-(layer, expert) reconstruction
    em = sm.experts
    D, F = 6, 10
    assert em.span_shape == (3, D, F)
    assert experts.shape == (L, E, 3, D, F)
    for layer in range(L):
        for e in range(E):
            got = paging.unflatten_expert_span(experts[layer, e], em)
            np.testing.assert_array_equal(got["wi"],
                                          tree["moe"]["wi"][layer, e])
            np.testing.assert_array_equal(got["wo"],
                                          tree["moe"]["wo"][layer, e])
    # batched gather unflattens with a leading expert axis
    sel = jnp.asarray([2, 0, 1], jnp.int32)
    got = paging.unflatten_expert_span(experts[1][sel], em)
    np.testing.assert_array_equal(got["wi"], tree["moe"]["wi"][1][sel])
    # the blocks the FFN reads in place: gate, up, then wo transposed
    wi, wo = tree["moe"]["wi"][2, 3], tree["moe"]["wo"][2, 3]
    for block, want in zip(experts[2, 3], (wi[:, 0], wi[:, 1], wo.T)):
        np.testing.assert_array_equal(block, want)
    # ... and the same blocks by leaf name, as the fetch hands them over
    leaves = em.leaf_blocks(lambda i: experts[2, 3][i])
    assert sorted(leaves) == ["wi", "wo"]
    for got, want in zip(leaves["wi"] + leaves["wo"],
                         (wi[:, 0], wi[:, 1], wo.T)):
        np.testing.assert_array_equal(got, want)


def test_split_pack_without_experts_matches_whole_layer(rng):
    """A dense group split-packs to shared-only (experts=None), identical
    to the whole-layer manifest."""
    tree = {"ffn": {"wi": jnp.asarray(rng.normal(0, 1, (2, 4, 8)),
                                      jnp.float32)}}
    shared, experts, sm = paging.pack_layer_stack_split(tree, 32)
    assert experts is None and sm.experts is None
    whole, manifest = paging.pack_layer_stack(tree, 32)
    np.testing.assert_array_equal(shared, whole)
    assert sm.shared == manifest


def test_expert_paged_forward_int8_scales_survive(rng):
    """int8 experts: the float32 dequant scales must NOT ride in the
    int8-packed expert pool (that cast truncates them to zero) — they
    stay in the shared span and are gathered per activated expert, so
    the expert-granular forward matches the resident forward."""
    from repro.configs import get_config
    from repro.models import forward, unembed
    from repro.models.params import init_params
    cfg = dataclasses.replace(get_config("mixtral-8x7b").smoke(),
                              dtype="float32", expert_dtype="int8")
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (2, 8)), jnp.int32)
    ref = unembed(cfg, params, forward(cfg, params, toks)["hidden"])
    assert float(jnp.max(jnp.abs(ref))) > 0
    pw = paging.pack_block_groups_split(params["blocks"], 4096)
    em = pw.expert_manifests["p0"]
    assert {e.path[-1] for e in em.leaves} == {"wi", "wo"}
    assert str(pw.expert_pages["p0"].dtype) == "int8"
    got = unembed(cfg, params,
                  forward(cfg, params, toks, paged_blocks=pw)["hidden"])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_pack_block_groups_split_shapes(rng):
    pw = paging.pack_block_groups_split({"p0": _moe_group(rng)}, 64)
    assert set(pw.expert_manifests) == {"p0"}
    em = pw.expert_manifests["p0"]
    assert pw.pages["p0"].shape[0] == em.num_layers == 3
    assert em.num_experts == 4
    assert pw.expert_pages["p0"].shape == (3, 4) + em.span_shape
    assert em.span_bytes == 3 * 6 * 10 * 4       # no padding
    assert pw.shared_layer_bytes("p0") == \
        pw.manifests["p0"].pages_per_layer * 64 * 4
