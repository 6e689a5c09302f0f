"""The Mixtral cell's architecture module is pinned to what the harness
drew, counted and read before its layout, counts and reference moved
into bench/references/moe_gqa.py: the sizes, the seeded weights bit for
bit, and the reference's gaps on fixed sequences bit for bit (recorded on
the CPU at tiny widths)."""
import hashlib

import jax
import numpy as np
import pytest

from conftest import TINY_CONFIG

from bench import spec
from bench.references import moe_gqa

SEED = 2 ** 33 + 7
TINY_DIMS = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab_size": 512,
             "num_experts": 8, "top_k": 2, "num_layers": 2,
             "rope_theta": 1000000.0, "norm_eps": 1e-05}
# sha256 of each leaf's bytes, first 16 hex digits
DRAW = {
    "top": {"['embed']['tokens']": "0f4d930af3b16144",
            "['final_norm']['scale']": "e220282eff0f7246",
            "['lm_head']": "60b5167196aa5862"},
    "layer 1": {"['attn']['wk']": "9a2b4b7e14209a0c",
                "['attn']['wo']": "d3a0b61de7e7fa3a",
                "['attn']['wq']": "32b7235b6de68b8d",
                "['attn']['wv']": "906e8d3356394142",
                "['attn_norm']['scale']": "3d37375e86bdb32d",
                "['ffn_norm']['scale']": "e2caf72a4bb18605",
                "['moe']['router']": "326c3a03bf266086",
                "['moe']['wi']": "930b1bb4c215dc2e",
                "['moe']['wo']": "59edf111c2a8742d"},
}
SEQS = [(np.array([5, 17, 300, 2, 41], np.int32), [7, 7, 100]),
        (np.array([9, 8, 7, 6, 5, 4, 3, 2, 1], np.int32), [511, 0, 256, 3])]
GAPS = {
    False: [[3.1242947578430176, 3.8035669326782227, 2.8415496349334717],
            [1.9945218563079834, 3.833834171295166, 2.7417497634887695,
             4.904820442199707]],
    True: [[0.002753019332885742, 0.0, 0.0],
           [0.0, 0.0, 0.0, 0.12562060356140137]],
}


def _digest(tree) -> dict:
    return {jax.tree_util.keystr(path):
            hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_dims_are_the_harness_sizes():
    assert moe_gqa.dims(spec.model_config(TINY_CONFIG)) == TINY_DIMS


@pytest.mark.parametrize("part", sorted(DRAW))
def test_draw_is_bit_identical(part):
    tree = (moe_gqa.top(TINY_DIMS, SEED) if part == "top"
            else moe_gqa.layer(TINY_DIMS, SEED, 1))
    assert _digest(tree) == DRAW[part]


@pytest.mark.parametrize("control", [False, True])
def test_gaps_are_bit_identical(control):
    got = moe_gqa.gaps(TINY_DIMS, SEED, SEQS, control=control)
    assert len(got) == len(GAPS[control])
    for row, want in zip(got, GAPS[control]):
        assert row.dtype == np.float32
        np.testing.assert_array_equal(row, np.asarray(want, np.float32))


def test_engine_params_stack_the_drawn_layers():
    from repro.models.params import abstract_params

    cfg = spec.model_config(TINY_CONFIG)
    tree = moe_gqa.engine_params(cfg, TINY_DIMS, SEED)
    shape = lambda a: (tuple(a.shape), str(a.dtype))  # noqa: E731
    assert jax.tree.map(shape, tree) == \
        jax.tree.map(shape, abstract_params(cfg))
    assert _digest({k: v for k, v in tree.items() if k != "blocks"}) == \
        DRAW["top"]
    for i in range(TINY_DIMS["num_layers"]):
        drawn = jax.device_get(moe_gqa.layer(TINY_DIMS, SEED, i))
        stacked = jax.tree.map(lambda a: a[i], tree["blocks"]["p0"])
        assert _digest(stacked) == _digest(drawn)
