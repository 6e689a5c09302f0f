"""MoE: router semantics, dense-vs-grouped equivalence, EP shard bodies on
a 1-device mesh, capacity drop accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import moe
from repro.models.params import init_params


def _moe_params(cfg, key=0):
    params = init_params(cfg, jax.random.key(key))
    return jax.tree.map(lambda a: a[0], params["blocks"]["p0"]["moe"])


@pytest.fixture(scope="module")
def mixtral_smoke():
    return dataclasses.replace(get_config("mixtral-8x7b").smoke(),
                               dtype="float32")


def test_router_topk_and_weights(mixtral_smoke, rng):
    cfg = mixtral_smoke
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 1, (32, cfg.d_model)), jnp.float32)
    w, idx, aux = moe.route(cfg, p["router"], x)
    assert w.shape == (32, cfg.top_k) and idx.shape == (32, cfg.top_k)
    assert float(jnp.min(w)) >= 0
    # softmax routing: top-k weights sum <= 1
    assert float(jnp.max(jnp.sum(w, -1))) <= 1.0 + 1e-5
    # distinct experts per token
    assert bool(jnp.all(idx[:, 0] != idx[:, 1]))
    assert float(aux) >= 1.0 - 1e-3     # lower bound: perfectly balanced


def test_sigmoid_router_renormalizes(rng):
    cfg = dataclasses.replace(get_config("deepseek-v3-671b").smoke(),
                              dtype="float32")
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 1, (16, cfg.d_model)), jnp.float32)
    w, idx, _ = moe.route(cfg, p["router"], x)
    np.testing.assert_allclose(jnp.sum(w, -1), jnp.ones(16), rtol=1e-5)


def test_dense_vs_grouped(mixtral_smoke, rng):
    cfg = mixtral_smoke
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 0.5, (64, cfg.d_model)), jnp.float32)
    yd, _ = moe.moe_dense(cfg, p, x)
    yg, _ = moe.moe_grouped(cfg, p, x, capacity_factor=8.0)  # no drops
    np.testing.assert_allclose(yd, yg, rtol=2e-4, atol=2e-4)


def test_grouped_capacity_drops_reduce_output(mixtral_smoke, rng):
    cfg = mixtral_smoke
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 0.5, (64, cfg.d_model)), jnp.float32)
    y_full, _ = moe.moe_grouped(cfg, p, x, capacity_factor=8.0)
    y_tight, _ = moe.moe_grouped(cfg, p, x, capacity_factor=0.25)
    # tight capacity must drop some tokens' expert contributions
    assert float(jnp.max(jnp.abs(y_full - y_tight))) > 1e-6


@pytest.mark.parametrize("variant", ["ep_psum", "ep_a2a"])
def test_ep_bodies_match_dense_on_unit_mesh(mixtral_smoke, rng, variant):
    """With a single shard and drop-free capacity the EP bodies must agree
    with the dense oracle (all_to_all and psum are identities)."""
    cfg = mixtral_smoke
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 0.5, (4, 8, cfg.d_model)), jnp.float32)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from repro.distributed.collectives import make_moe_shard_fn
    fn = make_moe_shard_fn(mesh, cfg, variant=variant, dp_axes=(),
                           expert_axes=("model",), capacity_factor=8.0)
    y, aux = fn(cfg, p, x)
    yd, auxd = moe.moe_dense(cfg, p, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), yd,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux), float(auxd), rtol=1e-3)


def test_shared_expert_added(rng):
    cfg = dataclasses.replace(get_config("deepseek-v3-671b").smoke(),
                              dtype="float32")
    p = _moe_params(cfg)
    x = jnp.asarray(rng.normal(0, 0.5, (8, cfg.d_model)), jnp.float32)
    y, _ = moe.moe_dense(cfg, p, x)
    p2 = dict(p)
    p2["shared"] = jax.tree.map(jnp.zeros_like, p["shared"])
    y2, _ = moe.moe_dense(cfg, p2, x)
    assert float(jnp.max(jnp.abs(y - y2))) > 1e-6
