"""Seed helpers for a configuration's weights.

A configuration's reference module (``bench/references/<reference>.py``)
owns its weight layout: it draws each layer, on the device and in one
jitted call, from these helpers and the run's seed, in the type the
weights are served in (bfloat16), and builds the engine's tree from the
drawn layers.  Linear weights are normal with standard deviation
1/sqrt(fan-in); norm scales are 1 + N(0, 0.1^2), so a dropped norm
weight shows.
"""
from __future__ import annotations

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


def normal(key, shape, fan_in):
    """A linear weight: N(0, 1/fan_in), in the served type."""
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(DTYPE)


def scale(key, n):
    """A norm scale of ``n`` entries: 1 + N(0, 0.1^2), in the served type."""
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(DTYPE)
