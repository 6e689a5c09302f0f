"""Seeded weights of a configuration, made on the device.

One jitted call per layer draws that layer's weights from the run's
seed, in the type they are served in (bfloat16).  The serving harness
copies each layer to host memory before it draws the next, so the device
never holds the whole unpacked model; the reference draws the same
layers again, one at a time, after the engine is gone.

Layout (per layer): ``attn`` {wq (D, H*Dh), wk, wv (D, Hkv*Dh), wo
(H*Dh, D)}, ``attn_norm``/``ffn_norm`` {scale (D,)}, ``moe`` {router
(D, E), wi (E, D, 2, F) with gate at [..., 0, :] and up at [..., 1, :],
wo (E, F, D)}; top level: ``embed`` {tokens (V, D)}, ``final_norm``
{scale (D,)}, ``lm_head`` (D, V).  Linear weights are normal with
standard deviation 1/sqrt(fan-in); norm scales are 1 + N(0, 0.1^2), so a
dropped norm weight shows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def base_key(seed: int) -> jax.Array:
    """A key for any whole number up to 64 bits: the low and high 32-bit
    words both enter it."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.key(lo), hi)


def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(DTYPE)


def _scale(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(DTYPE)


def _layer(key, *, D, H, Hkv, Dh, F, E):
    k = jax.random.split(key, 9)
    return {
        "attn": {"wq": _normal(k[0], (D, H * Dh), D),
                 "wk": _normal(k[1], (D, Hkv * Dh), D),
                 "wv": _normal(k[2], (D, Hkv * Dh), D),
                 "wo": _normal(k[3], (H * Dh, D), H * Dh)},
        "attn_norm": {"scale": _scale(k[4], D)},
        "ffn_norm": {"scale": _scale(k[5], D)},
        "moe": {"router": _normal(k[6], (D, E), D),
                "wi": _normal(k[7], (E, D, 2, F), D),
                "wo": _normal(k[8], (E, F, D), F)},
    }


def _top(key, *, D, V):
    k = jax.random.split(key, 3)
    return {"embed": {"tokens": _normal(k[0], (V, D), D)},
            "final_norm": {"scale": _scale(k[1], D)},
            "lm_head": _normal(k[2], (D, V), D)}


def _shape_kw(dims):
    return dict(D=dims["d_model"], H=dims["num_heads"],
                Hkv=dims["num_kv_heads"], Dh=dims["head_dim"],
                F=dims["d_ff"], E=dims["num_experts"])


@functools.lru_cache(maxsize=None)
def _layer_fn(D, H, Hkv, Dh, F, E):
    return jax.jit(functools.partial(_layer, D=D, H=H, Hkv=Hkv, Dh=Dh, F=F,
                                     E=E))


@functools.lru_cache(maxsize=None)
def _top_fn(D, V):
    return jax.jit(functools.partial(_top, D=D, V=V))


def layer(dims: dict, seed: int, index: int) -> dict:
    """Weights of layer ``index``, on the device."""
    key = jax.random.fold_in(base_key(seed), index + 1)
    return _layer_fn(**_shape_kw(dims))(key)


def top(dims: dict, seed: int) -> dict:
    """Embedding, final norm and output head, on the device."""
    key = jax.random.fold_in(base_key(seed), 0)
    return _top_fn(dims["d_model"], dims["vocab_size"])(key)
