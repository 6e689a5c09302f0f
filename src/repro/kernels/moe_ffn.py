"""Pallas grouped MoE-FFN kernel (TPU target, interpret-validated on CPU).

The TPU analogue of the paper's paged MoE-FFN GPU kernel (Appendix A.1,
Fig. 11): tokens arrive capacity-bucketed per expert as (E, C, D); the
kernel walks experts on the outer grid dimension — with paged weights,
each expert's (wi, wo) pages are exactly the units the CGOPipe weight
streamer double-buffers, so the grid order IS the page-consumption order.

Tiling: grid (E, C/bc, F/bf).  For each (expert, token-block) the F
dimension is the innermost (sequential) loop: the gate/up projections for
an F-tile are computed, activated, multiplied, and immediately folded into
the (bc, D) output accumulator via the down-projection tile — the (bc, F)
hidden activation never exists in HBM.  VMEM per step ≈
bc*D + D*2*bf + bf*D + bc*D(f32 acc), MXU-aligned for bf, bc multiples
of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wi_ref, wo_ref, si_ref, so_ref, o_ref, acc, *,
            act: str, blocks_f: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0].astype(jnp.float32)          # (bc, D)
    wi = wi_ref[0].astype(jnp.float32)        # (D, 2, bf)  (int8 ok)
    wo = wo_ref[0].astype(jnp.float32)        # (bf, D)

    h = jax.lax.dot_general(x, wi.reshape(x.shape[1], -1),
                            (((1,), (0,)), ((), ())))       # (bc, 2*bf)
    # fused weight-only dequant: per-expert scale applied to the matmul
    # OUTPUT tile — the bf16/int8 weights never materialize dequantized
    e = pl.program_id(0)
    h = h * si_ref[e]
    bf = wi.shape[2]
    gate, up = h[:, :bf], h[:, bf:]
    if act == "silu":
        g = gate * jax.nn.sigmoid(gate)
    else:                                     # gelu (tanh approx)
        g = jax.nn.gelu(gate, approximate=True)
    y = g * up                                # (bc, bf)
    acc[...] += jax.lax.dot_general(y, wo,
                                    (((1,), (0,)), ((), ()))) * so_ref[e]

    @pl.when(f == blocks_f - 1)
    def _fin():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def moe_ffn(xbuf, wi, wo, *, wi_scale=None, wo_scale=None, act: str = "silu",
            block_c: int = 128, block_f: int = 512, interpret: bool):
    """xbuf: (E,C,D); wi: (E,D,2,F); wo: (E,F,D) -> (E,C,D).

    wi/wo may be int8 (weight-only quantization): pass per-expert
    wi_scale/wo_scale (E,) f32 and the dequant is fused into the tile
    loop — the paper's §3.3 intensity-raising lever with zero extra HBM
    traffic.  The (E,) scale vectors ride whole in SMEM (a rank-1 (1,)
    VMEM block is not a legal TPU tile).

    NOTE on the (D,2,F) layout: the kernel reshapes its (D,2,bf) tile to
    (D, 2*bf) for one MXU matmul; gate rows are h[:, :bf], up rows are
    h[:, bf:], matching the model-side convention.
    """
    E, C, D = xbuf.shape
    F = wo.shape[1]
    if wi_scale is None:
        wi_scale = jnp.ones((E,), jnp.float32)
    if wo_scale is None:
        wo_scale = jnp.ones((E,), jnp.float32)
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    # pad C/F to block multiples
    pc = (-C) % block_c
    pf = (-F) % block_f
    if pc:
        xbuf = jnp.pad(xbuf, ((0, 0), (0, pc), (0, 0)))
    if pf:
        wi = jnp.pad(wi, ((0, 0), (0, 0), (0, 0), (0, pf)))
        wo = jnp.pad(wo, ((0, 0), (0, pf), (0, 0)))
    Cp, Fp = C + pc, F + pf
    grid = (E, Cp // block_c, Fp // block_f)
    kern = functools.partial(_kernel, act=act, blocks_f=Fp // block_f)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, D, 2, block_f), lambda e, c, f: (e, 0, 0, f)),
            pl.BlockSpec((1, block_f, D), lambda e, c, f: (e, f, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, D), xbuf.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, D), jnp.float32)],
        interpret=interpret,
    )(xbuf, wi, wo, wi_scale.astype(jnp.float32),
      wo_scale.astype(jnp.float32))
    return out[:, :C] if pc else out
