"""Placement plans: which tensors live where (paper policy → execution).

A `PlacementPlan` realizes the policy tuple's r_w/r_c fractions as a
per-leaf assignment of weights (and the KV cache) to memory levels, and —
on TPU — produces shardings with an explicit ``memory_kind`` so XLA
keeps offloaded tensors in host DRAM; jitted steps move what they use to
device memory explicitly (``device_operand``).  On the CPU validation
backend the plan is exercised logically (page store + engine double
buffer) and its timing modeled by core.cgopipe / core.hrm — see
DESIGN.md §2.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import Policy, kv_bytes_per_token_layer, model_bytes


def supports_host_offload() -> bool:
    """Whether the serving engine keeps its host tier in pinned_host
    memory: chosen by platform, not by the listed memory kinds.  On TPU
    the tier is required (``_make_pinned_sharding`` raises when the space
    is missing).  Other backends have none the engine can use — jax's CPU
    backend lists ``pinned_host`` but can neither run the placement
    annotations nor mix memory spaces in one op — so there the host tier
    is plain numpy / device arrays."""
    return jax.devices()[0].platform == "tpu"


class HostOffloadFallbackWarning(UserWarning):
    """The backend has no pinned_host tier the engine can use: host-tier
    stores use default placement (pageable numpy / device arrays)."""


_warned_no_pinned = False


def reset_host_probe() -> None:
    """Clear the process-wide fall-back warning latch.  The degradation
    ladder's re-promotion path calls this before re-probing, so the
    pinned→pageable fall-back is observable each time it recurs instead
    of once per process (and a recovered backend probes clean)."""
    global _warned_no_pinned
    _warned_no_pinned = False


def _make_pinned_sharding() -> jax.sharding.Sharding:
    """Single-device sharding in the pinned_host memory space (split out
    so tests can monkeypatch it with a plain CPU sharding and drive the
    pinned code paths on backends without the memory space).  A missing
    space is an error: on TPU the host tier must not silently become a
    pageable one."""
    dev = jax.devices()[0]
    kinds = [m.kind for m in dev.addressable_memories()]
    if "pinned_host" not in kinds:
        raise RuntimeError(f"{dev.device_kind} exposes no pinned_host memory "
                           f"space (kinds: {kinds})")
    return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")


def pinned_host_sharding(*, warn: bool = True, faults=None
                         ) -> Optional[jax.sharding.Sharding]:
    """Sharding for host-tier staging buffers, or None off TPU (one
    structured warning per process, until ``reset_host_probe``).
    ``faults`` is an optional runtime.faults.FaultInjector: the
    "host_alloc" site models a failed pinned-host allocation (raises
    HostMemoryError) — the caller falls back to the pageable tier and may
    re-probe on ladder promotion."""
    global _warned_no_pinned
    if faults is not None:
        faults.raise_for("host_alloc")
    if supports_host_offload():
        return _make_pinned_sharding()
    if warn and not _warned_no_pinned:
        _warned_no_pinned = True
        warnings.warn(
            "backend %r has no pinned_host memory space the host tier can "
            "use — host-tier KV blocks and weight pages use default "
            "placement" % jax.default_backend(),
            HostOffloadFallbackWarning, stacklevel=2)
    return None


def pinned_put(x):
    """Place an array in pinned host memory when available; otherwise
    return it as a jax array in default placement (post-warning)."""
    s = pinned_host_sharding()
    if s is None:
        return jnp.asarray(x)
    return jax.device_put(x, s)


def to_device(x):
    """Stage a (possibly pinned-host) array into device memory."""
    return jax.device_put(x, jax.devices()[0])


def in_host_memory(x) -> bool:
    """True for a traced operand that lives in host memory (a slice of a
    pinned_host page store inside a jitted step)."""
    return jax.typeof(x).memory_space == jax.memory.Space.Host


def device_operand(x):
    """Inside a jitted step: the explicit host→device transfer of a host
    memory operand before compute uses it (XLA refuses compute that mixes
    memory spaces); device operands pass through untouched."""
    if in_host_memory(x):
        return jax.device_put(x, jax.memory.Space.Device)
    return x


@dataclass
class PlacementPlan:
    """Per-leaf device residency for the offloaded-serving engine."""
    device_leaves: List[Tuple[str, ...]]
    host_leaves: List[Tuple[str, ...]]
    kv_on_device: bool
    w_device_bytes: float
    w_host_bytes: float

    @property
    def host_fraction(self) -> float:
        tot = self.w_device_bytes + self.w_host_bytes
        return self.w_host_bytes / tot if tot else 0.0


def _leaf_sizes(tree, prefix=()):
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _leaf_sizes(tree[k], prefix + (k,))
        return out
    size = int(np.prod(tree.shape)) * np.dtype(tree.dtype).itemsize
    return [(prefix, size)]


def plan_from_policy(cfg: ModelConfig, abstract_params, pol: Policy
                     ) -> PlacementPlan:
    """Greedy knapsack: keep the hottest (non-expert first, then experts)
    leaves on device until the r_w budget is spent.  Expert weights are the
    paper's primary offload target (largest, least intensity per byte)."""
    sizes = _leaf_sizes(abstract_params)
    total = sum(s for _, s in sizes)
    budget = pol.w_gpu_ratio * total

    def priority(path):                       # lower = keep on device first
        if "moe" in path and path[-1] in ("wi", "wo"):
            return 2                           # experts offload first
        if path[0] in ("embed", "lm_head"):
            return 1
        return 0

    ordered = sorted(sizes, key=lambda e: (priority(e[0]), -e[1]))
    device, host, spent = [], [], 0.0
    for path, size in ordered:
        if spent + size <= budget:
            device.append(path)
            spent += size
        else:
            host.append(path)
    return PlacementPlan(device, host, kv_on_device=pol.kv_gpu_ratio >= 1.0,
                         w_device_bytes=spent, w_host_bytes=total - spent)
