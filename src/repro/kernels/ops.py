"""jit'd public wrappers for the Pallas kernels.

Dispatch: on TPU the compiled kernels run natively; off TPU the kernel
bodies run only when asked for explicitly (``impl="interpret"``, the
Pallas interpreter, for correctness validation), and ``auto`` takes the
jnp reference path.  Every dispatcher shares one ``impl`` contract:

  * ``auto``      — Pallas on TPU, the jnp reference path elsewhere;
  * ``pallas``    — the kernel, compiled natively; an error off TPU;
  * ``interpret`` — the kernel body under the Pallas interpreter;
  * ``ref``       — the pure-jnp oracle.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from repro.kernels import gqa_decode as _gqa
from repro.kernels import moe_ffn as _moe
from repro.kernels import paged_decode as _paged
from repro.kernels import ref as _ref
from repro.models import kvcache as _kvcache

_IMPLS = ("auto", "pallas", "interpret", "ref")


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# -- measured dense-vs-paged crossover (benchmarks/bench_transfer.py) -------
#
# The paged kernel gathers mapped_blocks × block_bytes; the dense path reads
# the whole B × max_seq ring but with simpler addressing.  On real devices
# there is an occupancy above which dense wins; bench_transfer.py measures
# it, a caller installs it explicitly (``load_paged_crossover``), and
# engines resolve impl='auto' against it at init (host-side — the impl
# string stays a static jit arg).  Unmeasured -> always-paged on TPU.

_CROSSOVER: dict = {"occ": None}


def set_paged_crossover(occupancy) -> None:
    """Install (or clear, with None) the measured occupancy threshold at
    which the dense-view path overtakes the paged kernel."""
    _CROSSOVER["occ"] = None if occupancy is None else float(occupancy)


def load_paged_crossover(path: str = "BENCH_transfer.json"):
    """Load the measured crossover from a bench_transfer artifact.  Missing
    or malformed file (or a null measurement — interpret-mode runs record
    none) leaves the threshold unset and returns None."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    occ = data.get("crossover_occupancy")
    if occ is not None:
        set_paged_crossover(occ)
    return _CROSSOVER["occ"]


def paged_auto_impl(occupancy: float) -> str:
    """Resolve impl='auto' for paged decode from the measured crossover.

    Off-TPU the jnp dense-view oracle is always the fast path ('ref').  On
    TPU: the paged kernel below the measured crossover occupancy, the dense
    view at/above it; with no measurement on record, always the kernel
    (paged is the byte-count-optimal default the benches validated)."""
    if not on_tpu():
        return "ref"
    thr = _CROSSOVER["occ"]
    if thr is not None and occupancy >= thr:
        return "ref"
    return "pallas"


def _resolve_impl(impl: str):
    """The shared impl contract: returns (use_ref, interpret)."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    tpu = on_tpu()
    if impl == "pallas" and not tpu:
        raise ValueError("impl='pallas' compiles the kernel for a TPU, but "
                         f"the backend is {jax.default_backend()!r}; use "
                         "impl='interpret' to run the kernel body here")
    use_ref = impl == "ref" or (impl == "auto" and not tpu)
    return use_ref, impl == "interpret"


@functools.partial(jax.jit, static_argnames=("scale", "attn_softcap",
                                             "block_w", "impl"))
def gqa_decode(q, k, v, valid, *, scale: float, attn_softcap: float = 0.0,
               k_scale=None, v_scale=None, block_w: int = 512,
               impl: str = "auto"):
    """Flash-decode GQA partials. impl: auto | pallas | interpret | ref.
    int8 KV passes k_scale/v_scale (B,W,Hkv) f32 — dequant folds into the
    tiles in both the kernel and the ref path."""
    use_ref, interpret = _resolve_impl(impl)
    if use_ref:
        return _ref.gqa_decode_ref(q, k, v, valid, scale=scale,
                                   attn_softcap=attn_softcap,
                                   k_scale=k_scale, v_scale=v_scale)
    return _gqa.gqa_decode(q, k, v, valid, scale=scale,
                           attn_softcap=attn_softcap, k_scale=k_scale,
                           v_scale=v_scale, block_w=block_w,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "attn_softcap",
                                             "window", "impl"))
def paged_gqa_decode(q, layer_cache, pos, *, scale: float,
                     attn_softcap: float = 0.0, window: int = 0,
                     impl: str = "auto"):
    """Paged flash-decode GQA partials, straight through the page table.

    q: (B,H,D); layer_cache: a paged layer-cache slice — head-major block
    arena leaves ``k``/``v`` (Hkv, NB, bt, D*) (+ ``k_scale``/``v_scale``
    (Hkv, NB, bt) for int8), ``slot_pos`` (NB, bt), and ``page_table``
    (B, MB); pos: (B,) decode positions.  Returns the
    ``attention_partials`` triple.

    impl ``ref`` (and ``auto`` off-TPU) is the dense-view oracle: the
    old ``kvcache.paged_view`` + ``attention_partials`` composition —
    the Pallas path gathers only the mapped blocks instead."""
    use_ref, interpret = _resolve_impl(impl)
    if use_ref:
        return _ref.paged_gqa_decode_ref(q, layer_cache, pos, scale=scale,
                                         attn_softcap=attn_softcap,
                                         window=window)
    return _paged.paged_gqa_decode(
        q, layer_cache["k"], layer_cache["v"], layer_cache["slot_pos"],
        layer_cache["page_table"], pos, scale=scale,
        attn_softcap=attn_softcap, window=window,
        k_scale=layer_cache.get("k_scale"),
        v_scale=layer_cache.get("v_scale"), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "lat", "impl"))
def paged_mla_decode(qcat, layer_cache, pos, *, scale: float, lat: int,
                     impl: str = "auto"):
    """Paged absorbed-MLA decode partials through the page table.

    qcat: (B,H,lat+dr) — absorbed latent queries ++ rope queries;
    layer_cache: paged MLA slice (``ckv`` (NB, bt, lat), ``kr``
    (NB, bt, dr), ``slot_pos``, ``page_table``); pos: (B,).  The value
    is the latent itself (Dv = lat).  impl contract as above."""
    use_ref, interpret = _resolve_impl(impl)
    if use_ref:
        return _ref.paged_mla_decode_ref(qcat, layer_cache, pos,
                                         scale=scale)
    return _paged.paged_mla_decode(
        qcat, layer_cache["ckv"], layer_cache["kr"],
        layer_cache["slot_pos"], layer_cache["page_table"], pos,
        scale=scale, lat=lat, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "attn_softcap",
                                             "window", "impl"))
def paged_gqa_decode_fused(q, layer_cache, new, pos, *, scale: float,
                           attn_softcap: float = 0.0, window: int = 0,
                           impl: str = "auto"):
    """Fused decode-write paged GQA: one compiled step that both attends
    over the fresh token and scatters it into the arena — decode no longer
    dispatches ``kvcache.write_decode_paged`` separately before attention.

    q: (B,H,D); layer_cache: the *pre-write* paged slice (head-major
    arena); new: the decode-step write dict — ``k``/``v`` (B,1,Hkv,D*)
    (+ ``k_scale``/``v_scale`` (B,1,Hkv) for int8); pos: (B,).  Returns
    ``((o_unnorm, m, l), new_cache)``.

    Bit-identity: the kernel merges the fresh token (pre-cast to the arena
    dtype, exactly as the scatter casts it) into its target block's tile
    in-register before any score math, so attention over the un-written
    arena equals write-then-attend term-by-term; the ref branch simply
    scatters first and runs the dense-view oracle."""
    use_ref, interpret = _resolve_impl(impl)
    new_cache = _kvcache._decode_scatter(layer_cache, new, pos)
    if use_ref:
        part = _ref.paged_gqa_decode_ref(q, new_cache, pos, scale=scale,
                                         attn_softcap=attn_softcap,
                                         window=window)
        return part, new_cache
    kw = {}
    if "k_scale" in layer_cache:
        kw = dict(k_scale=layer_cache["k_scale"],
                  v_scale=layer_cache["v_scale"],
                  k_scale_new=new["k_scale"][:, 0].astype(jnp.float32),
                  v_scale_new=new["v_scale"][:, 0].astype(jnp.float32))
    part = _paged.paged_gqa_decode(
        q, layer_cache["k"], layer_cache["v"], layer_cache["slot_pos"],
        layer_cache["page_table"], pos, scale=scale,
        attn_softcap=attn_softcap, window=window,
        k_new=new["k"][:, 0].astype(layer_cache["k"].dtype),
        v_new=new["v"][:, 0].astype(layer_cache["v"].dtype),
        interpret=interpret, **kw)
    return part, new_cache


@functools.partial(jax.jit, static_argnames=("scale", "lat", "impl"))
def paged_mla_decode_fused(qcat, layer_cache, new, pos, *, scale: float,
                           lat: int, impl: str = "auto"):
    """Fused decode-write paged MLA (see ``paged_gqa_decode_fused``).
    new: ``ckv`` (B,1,lat) / ``kr`` (B,1,dr).  Returns
    ``((o_unnorm, m, l), new_cache)``."""
    use_ref, interpret = _resolve_impl(impl)
    new_cache = _kvcache._decode_scatter(layer_cache, new, pos)
    if use_ref:
        part = _ref.paged_mla_decode_ref(qcat, new_cache, pos, scale=scale)
        return part, new_cache
    part = _paged.paged_mla_decode(
        qcat, layer_cache["ckv"], layer_cache["kr"],
        layer_cache["slot_pos"], layer_cache["page_table"], pos,
        scale=scale, lat=lat,
        ckv_new=new["ckv"][:, 0].astype(layer_cache["ckv"].dtype),
        kr_new=new["kr"][:, 0].astype(layer_cache["kr"].dtype),
        interpret=interpret)
    return part, new_cache


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "impl"))
def moe_ffn(xbuf, wi, wo, wi_scale=None, wo_scale=None, *,
            act: str = "silu", block_c: int = 128,
            block_f: int = 512, impl: str = "auto"):
    """Grouped gated expert FFN (int8 weights + scales supported).
    impl: auto | pallas | interpret | ref."""
    use_ref, interpret = _resolve_impl(impl)
    if use_ref:
        if wi_scale is not None:
            wi = wi.astype(jnp.float32) * wi_scale[:, None, None, None]
            wo = wo.astype(jnp.float32) * wo_scale[:, None, None]
        return _ref.moe_ffn_ref(xbuf, wi, wo, act=act)
    return _moe.moe_ffn(xbuf, wi, wo, wi_scale=wi_scale, wo_scale=wo_scale,
                        act=act, block_c=block_c,
                        block_f=block_f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "attn_softcap", "scale", "block_q", "block_k",
    "impl"))
def flash_prefill(q, k, v, kv_len=None, *, causal: bool = True,
                  window: int = 0, attn_softcap: float = 0.0, scale=None,
                  block_q: int = 256, block_k: int = 256,
                  impl: str = "auto"):
    """Prefill/training flash attention. impl: auto | pallas | interpret |
    ref (ref = models.common.chunked_attention, the jnp tile-equivalent)."""
    use_ref, interpret = _resolve_impl(impl)
    if use_ref:
        from repro.models.common import chunked_attention
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 attn_softcap=attn_softcap, scale=scale,
                                 kv_len=kv_len)
    from repro.kernels.flash_prefill import flash_prefill as _fp
    return _fp(q, k, v, causal=causal, window=window,
               attn_softcap=attn_softcap, scale=scale, kv_len=kv_len,
               block_q=block_q, block_k=block_k, interpret=interpret)
