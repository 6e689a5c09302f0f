"""Pallas paged flash-decode kernels (TPU target, interpret-validated on
CPU): decode attention that reads K/V **directly through the
``(slot, logical_block) → physical_block`` page table** of the block-
granular KV arena (models.kvcache / core.blockpool), instead of first
gathering a dense ``max_seq``-wide ring view (``kvcache.paged_view``).

Grid layout: one grid step per (batch row, kv head, logical block).  The
page table and the per-row decode positions ride in scalar-prefetch SMEM
(``PrefetchScalarGridSpec``), so each K/V BlockSpec's index map picks the
arena slab ``pt[b, lb]`` *before* the kernel body runs — the block DMA is
issued straight against the physical block, and HBM traffic per step is
``mapped_blocks × block_bytes`` instead of ``B × max_seq`` row bytes.

Arena layout (head-major bt-tiling, ``kvcache.arena_block_axis``): K/V
arrive as ``(Hkv, NB, bt, D)`` and the scale planes as ``(Hkv, NB, bt)``,
so the per-(block, head) BlockSpec slab is a contiguous ``(bt, D)`` tile
whose trailing axes map onto (sublane, lane) natively for every block
size — no transpose sits on the hot path.

Masking invariants (mirrors what ``paged_view`` + ``decode_valid_mask``
compute on the dense view):

  * an **unmapped** logical block (``pt[b, lb] < 0``) clamps its index
    map to physical block 0 and masks the whole block in-kernel — the
    arena's trash block (the scatter target for masked rows) is *never
    read* by the gather side;
  * within a mapped block, validity is the usual
    ``slot_pos >= 0 & slot_pos <= pos`` ring test, evaluated on the
    block's own (1, bt) ``slot_pos`` slab.

**Fused decode-write epilogue**: passing the fresh decode token
(``k_new``/``v_new``, already cast to the arena dtype) merges it into
its target block's tile *in-register* — the tile each grid step computes
on is bit-identical to what the block would hold after
``kvcache.write_decode_paged``, so attention over the un-written arena
equals write-then-attend exactly (including the ring-wrap case, where
the merge shadows the stale token the scatter would overwrite, and the
unmapped case, where the scatter goes to the trash block and the gather
masks it).  The actual arena scatter then runs as part of the same
compiled step (``kernels.ops.paged_*_decode_fused``), not as a separate
dispatch before the kernel.

A running (max, sumexp, accumulator) online-softmax triple lives in VMEM
scratch across the sequential block grid dimension (same structure as
``gqa_decode``), and the kernels return *partials* ``(o_unnorm, m, l)``
— the ``attention_partials`` contract — so the sequence-sharded LSE
combine keeps working.

int8 KV: quantized arenas carry per-(token, head) ``k_scale``/``v_scale``
planes; the kernel folds them per block — ``s = (q·k_int) · k_scale`` and
``acc += (p · v_scale) @ v_int`` — instead of materializing a dequantized
ring (the same folding the jnp ref path applies, so the two agree
term-by-term).

MLA: the absorbed decode form is GQA with one kv head whose key is
``concat(ckv, kr)`` and whose value is ``ckv``; the kernel gathers the
latent and rope leaves per block and computes the score as two partial
dots (``q_lat·ckv + q_rope·kr``) — no concatenated ring is ever built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_decode_cost(B, H, blocks, bt, D, Dv):
    """pl.CostEstimate for one flash-decode dispatch: the score and value
    contractions over every gathered ring position, exp per score."""
    positions = B * blocks * bt
    return pl.CostEstimate(
        flops=2 * positions * H * (D + Dv),
        bytes_accessed=positions * (D + Dv) * 2 + B * H * (D + Dv) * 4,
        transcendentals=positions * H,
    )


# ---------------------------------------------------------------------------
# GQA (dense or int8 arena)
# ---------------------------------------------------------------------------

def _merge_masks(pt_ref, pos, b, w, bt, blocks_w):
    """The fused epilogue's target lanes: (bt, 1) over the K/V tile's
    token rows and (1, bt) over the slot_pos / scale rows, both true only
    at the fresh token's ring slot inside its mapped target block."""
    i = pos % (blocks_w * bt)
    hit = (w == i // bt) & (pt_ref[b, w] >= 0)
    rows = (jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) == i % bt) & hit
    lanes = (jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) == i % bt) & hit
    return rows, lanes


def _online_softmax_step(s, v, m_s, l_s, acc, vs=None):
    """One block of the flash-decode recurrence over scores s (G, bt) and
    values v (bt, Dv) f32; the (G, 1) max / sumexp and (G, Dv)
    accumulator live in VMEM scratch across the block grid dimension."""
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe) * (s > NEG_INF / 2)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    if vs is not None:
        p = p * vs
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))                        # (G, Dv)
    # keep the TRUE running max (NEG_INF while nothing valid yet): an
    # all-invalid early block must not clamp the max to 0, or a later
    # block with a negative true max would report m = 0 instead of the
    # oracle's max
    m_s[...] = m_new


def _gqa_kernel(pt_ref, pos_ref,                     # scalar prefetch (SMEM)
                q_ref, k_ref, v_ref, *rest,
                scale: float, attn_softcap: float, window: int,
                blocks_w: int, quantized: bool, fused: bool):
    rest = list(rest)
    kn_ref = vn_ref = kns_ref = vns_ref = None
    if fused:
        kn_ref, vn_ref = rest.pop(0), rest.pop(0)
    if quantized:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
        if fused:
            kns_ref, vns_ref = rest.pop(0), rest.pop(0)
    sp_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = rest
    b, w = pl.program_id(0), pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]
    sp = sp_ref[0]                                   # (1, bt) block's ring
    k = k_ref[0, 0]                                  # (bt, D) arena dtype
    v = v_ref[0, 0]                                  # (bt, Dv)
    if quantized:
        ks = ks_ref[0, 0]                            # (1, bt)
        vs = vs_ref[0, 0]
    if fused:
        # merge the fresh token into its target block's tile in-register:
        # the tile then equals the post-write_decode_paged block exactly
        # (k_new is pre-cast to the arena dtype), so attention over the
        # un-written arena is bit-identical to write-then-attend
        rows, lanes = _merge_masks(pt_ref, pos, b, w, k.shape[0], blocks_w)
        k = jnp.where(rows, kn_ref[0, 0], k)         # kn: (1, D)
        v = jnp.where(rows, vn_ref[0, 0], v)
        sp = jnp.where(lanes, pos, sp)
        if quantized:
            ks = jnp.where(lanes, kns_ref[0, 0], ks)  # kns: (1, 1)
            vs = jnp.where(lanes, vns_ref[0, 0], vs)
    valid = (sp >= 0) & (sp <= pos) & (pt_ref[b, w] >= 0)
    if window:
        valid &= sp > pos - window

    q = q_ref[0, 0].astype(jnp.float32) * scale      # (G, D)
    s = jax.lax.dot_general(q, k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())))         # (G, bt)
    if quantized:
        s = s * ks
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    s = jnp.where(valid, s, NEG_INF)
    _online_softmax_step(s, v.astype(jnp.float32), m_s, l_s, acc,
                         vs if quantized else None)

    @pl.when(w == blocks_w - 1)
    def _fin():
        o_ref[0, 0] = acc[...]
        m_ref[0, 0] = jnp.where(m_s[...] <= NEG_INF / 2, 0.0, m_s[...])
        l_ref[0, 0] = l_s[...]


def paged_gqa_decode(q, k, v, slot_pos, page_table, pos, *, scale: float,
                     attn_softcap: float = 0.0, window: int = 0,
                     k_scale=None, v_scale=None,
                     k_new=None, v_new=None,
                     k_scale_new=None, v_scale_new=None,
                     interpret: bool):
    """q: (B,H,D); k/v: (Hkv, NB, bt, D*) head-major block arena (last
    block = trash, never read); slot_pos: (NB, bt) int32; page_table:
    (B, MB) int32 (-1 = unmapped); pos: (B,) int32 query positions.  int8
    arenas pass k_scale/v_scale (Hkv, NB, bt) f32.  The fused decode-write
    epilogue passes the fresh token k_new/v_new (B, Hkv, D*) — already in
    the arena dtype — (+ k_scale_new/v_scale_new (B, Hkv) for int8); it is
    merged into its target block's tile in-register.  Returns partials
    (o_unnorm (B,H,Dv) f32, m (B,H) f32, l (B,H) f32).

    TPU tiling: every block's last two dims must be (8, 128)-divisible or
    span the array's, so the per-block rows (slot_pos, scales, the fresh
    token) ride with a unit axis inserted before their minor dim and the
    (max, sumexp) partials come back as (B, Hkv, G, 1)."""
    B, H, D = q.shape
    Hkv, NB, bt, Dv = v.shape
    MB = page_table.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    quantized = k_scale is not None
    fused = k_new is not None
    page_table = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def idx_q(b, h, w, pt, ps):
        return (b, h, 0, 0)

    def idx_blk(b, h, w, pt, ps):
        # unmapped -> physical block 0, fully masked in-kernel (the trash
        # block at the arena's end is a scatter-only target)
        return (h, jnp.maximum(pt[b, w], 0), 0, 0)

    def idx_sp(b, h, w, pt, ps):
        return (jnp.maximum(pt[b, w], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), idx_q),
        pl.BlockSpec((1, 1, bt, D), idx_blk),
        pl.BlockSpec((1, 1, bt, Dv), idx_blk),
    ]
    inputs = [qg, k, v]
    if fused:
        in_specs += [pl.BlockSpec((1, 1, 1, D), idx_q),
                     pl.BlockSpec((1, 1, 1, Dv), idx_q)]
        inputs += [k_new.reshape(B, Hkv, 1, D), v_new.reshape(B, Hkv, 1, Dv)]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, bt), idx_blk)] * 2
        inputs += [k_scale.reshape(Hkv, NB, 1, bt),
                   v_scale.reshape(Hkv, NB, 1, bt)]
        if fused:
            in_specs += [pl.BlockSpec((1, 1, 1, 1), idx_q)] * 2
            inputs += [k_scale_new.reshape(B, Hkv, 1, 1),
                       v_scale_new.reshape(B, Hkv, 1, 1)]
    in_specs.append(pl.BlockSpec((1, 1, bt), idx_sp))
    inputs.append(slot_pos.reshape(NB, 1, bt))

    kern = functools.partial(_gqa_kernel, scale=scale,
                             attn_softcap=attn_softcap, window=window,
                             blocks_w=MB, quantized=quantized, fused=fused)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, MB),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, 1, G, Dv), idx_q),
                pl.BlockSpec((1, 1, G, 1), idx_q),
                pl.BlockSpec((1, 1, G, 1), idx_q),
            ),
            scratch_shapes=[
                pltpu.VMEM((G, Dv), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, G, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ),
        cost_estimate=_flash_decode_cost(B, H, MB, bt, D, Dv),
        interpret=interpret,
        name="paged_gqa_decode",
    )(page_table, pos, *inputs)
    return o.reshape(B, H, Dv), m.reshape(B, H), l.reshape(B, H)


# ---------------------------------------------------------------------------
# MLA (absorbed decode over the latent arena)
# ---------------------------------------------------------------------------

def _mla_kernel(pt_ref, pos_ref,
                ql_ref, qr_ref, ckv_ref, kr_ref, *rest,
                scale: float, blocks_w: int, fused: bool):
    rest = list(rest)
    cn_ref = rn_ref = None
    if fused:
        cn_ref, rn_ref = rest.pop(0), rest.pop(0)
    sp_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = rest
    b, w = pl.program_id(0), pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]
    sp = sp_ref[0]                                   # (1, bt)
    ckv = ckv_ref[0]                                 # (bt, lat) arena dtype
    kr = kr_ref[0]                                   # (bt, dr)
    if fused:
        # in-register merge of the fresh latent — see the GQA kernel note
        rows, lanes = _merge_masks(pt_ref, pos, b, w, ckv.shape[0], blocks_w)
        ckv = jnp.where(rows, cn_ref[0], ckv)        # cn: (1, lat)
        kr = jnp.where(rows, rn_ref[0], kr)
        sp = jnp.where(lanes, pos, sp)
    valid = (sp >= 0) & (sp <= pos) & (pt_ref[b, w] >= 0)

    ql = ql_ref[0].astype(jnp.float32) * scale       # (H, lat)
    qr = qr_ref[0].astype(jnp.float32) * scale       # (H, dr)
    ckv = ckv.astype(jnp.float32)
    # score against concat(ckv, kr) without building the concat: two
    # partial dots over the latent and rope halves
    s = jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ()))) \
        + jax.lax.dot_general(qr, kr.astype(jnp.float32),
                              (((1,), (1,)), ((), ())))
    s = jnp.where(valid, s, NEG_INF)
    _online_softmax_step(s, ckv, m_s, l_s, acc)

    @pl.when(w == blocks_w - 1)
    def _fin():
        o_ref[0] = acc[...]
        m_ref[0] = jnp.where(m_s[...] <= NEG_INF / 2, 0.0, m_s[...])
        l_ref[0] = l_s[...]


def paged_mla_decode(qcat, ckv, kr, slot_pos, page_table, pos, *,
                     scale: float, lat: int,
                     ckv_new=None, kr_new=None, interpret: bool):
    """Absorbed MLA decode over the latent block arena.  qcat:
    (B, H, lat + dr) — absorbed latent queries ++ rope queries; ckv:
    (NB, bt, lat); kr: (NB, bt, dr); slot_pos: (NB, bt); page_table:
    (B, MB); pos: (B,).  The fused decode-write epilogue passes the fresh
    latents ckv_new (B, lat) / kr_new (B, dr) in the arena dtype.  The
    attended value is the latent itself, so the partials come back as
    (o_unnorm (B,H,lat) f32, m, l).  The latent and rope query halves
    enter as separate blocks, so no lane slice is taken in-kernel."""
    B, H, _ = qcat.shape
    NB, bt, _ = ckv.shape
    dr = kr.shape[-1]
    MB = page_table.shape[1]
    fused = ckv_new is not None
    page_table = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def idx_row(b, w, pt, ps):
        return (b, 0, 0)

    def idx_blk(b, w, pt, ps):
        return (jnp.maximum(pt[b, w], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, lat), idx_row),
        pl.BlockSpec((1, H, dr), idx_row),
        pl.BlockSpec((1, bt, lat), idx_blk),
        pl.BlockSpec((1, bt, dr), idx_blk),
    ]
    inputs = [qcat[..., :lat], qcat[..., lat:], ckv, kr]
    if fused:
        in_specs += [pl.BlockSpec((1, 1, lat), idx_row),
                     pl.BlockSpec((1, 1, dr), idx_row)]
        inputs += [ckv_new.reshape(B, 1, lat), kr_new.reshape(B, 1, dr)]
    in_specs.append(pl.BlockSpec((1, 1, bt), idx_blk))
    inputs.append(slot_pos.reshape(NB, 1, bt))

    kern = functools.partial(_mla_kernel, scale=scale, blocks_w=MB,
                             fused=fused)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, MB),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, H, lat), idx_row),
                pl.BlockSpec((1, H, 1), idx_row),
                pl.BlockSpec((1, H, 1), idx_row),
            ),
            scratch_shapes=[
                pltpu.VMEM((H, lat), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, lat), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ),
        cost_estimate=_flash_decode_cost(B, H, MB, bt, lat + dr, lat),
        interpret=interpret,
        name="paged_mla_decode",
    )(page_table, pos, *inputs)
    return o, m.reshape(B, H), l.reshape(B, H)
