"""Gradient compression: quantization error bounds, error feedback
accumulation, psum correctness on a 1-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import (compressed_psum, quantize_int8,
                                           tree_compressed_psum)


@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1,
                max_size=64))
@settings(max_examples=50, deadline=None)
def test_int8_quantization_error_bound(xs):
    x = jnp.asarray(xs, jnp.float32)
    q, scale = quantize_int8(x)
    err = np.max(np.abs(np.asarray(q, np.float32) * scale - np.asarray(x)))
    assert err <= float(scale) * 0.5 + 1e-6


def _on_mesh(fn, *args):
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(P() for _ in args),
                         out_specs=(P(), P()), check_vma=False)(*args)


def test_compressed_psum_single_device_identity(rng):
    g = jnp.asarray(rng.normal(0, 1, (32,)), jnp.float32)
    out, err = _on_mesh(
        lambda x: compressed_psum(x, "pod", method="int8"), g)
    np.testing.assert_allclose(out + err, g, rtol=1e-5, atol=1e-5)
    # bf16 path
    out2, err2 = _on_mesh(
        lambda x: compressed_psum(x, "pod", method="bf16"), g)
    np.testing.assert_allclose(out2 + err2, g, rtol=1e-5, atol=1e-5)


def test_error_feedback_converges():
    """Summing compressed estimates WITH error feedback over T steps must
    track the true running sum to within one quantization step (the EF
    telescoping property)."""
    rng = np.random.default_rng(3)
    true_sum = np.zeros(16, np.float32)
    est_sum = np.zeros(16, np.float32)
    err = jnp.zeros(16)
    for _ in range(50):
        g = jnp.asarray(rng.normal(0, 1, (16,)), jnp.float32)
        true_sum += np.asarray(g)
        out, err = _on_mesh(
            lambda x, e: compressed_psum(x, "pod", method="int8", error=e),
            g, err)
        est_sum += np.asarray(out)
    # telescoping: |true - est| == |final error| <= one quant step
    resid = np.abs(true_sum - est_sum)
    assert np.max(resid) <= float(jnp.max(jnp.abs(err))) + 1e-4


def test_tree_compression_threads_state(rng):
    g = {"a": jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32),
         "b": {"c": jnp.asarray(rng.normal(0, 1, (4,)), jnp.float32)}}
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P

    def body(tree):
        return tree_compressed_psum(tree, "pod", method="bf16")

    out, errs = jax.shard_map(
        body, mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), g),),
        out_specs=(jax.tree.map(lambda _: P(), g),
                   jax.tree.map(lambda _: P(), g)), check_vma=False)(g)
    assert jax.tree.structure(out) == jax.tree.structure(g)
    for k in ("a",):
        np.testing.assert_allclose(out[k] + errs[k], g[k], rtol=1e-5,
                                   atol=1e-5)
