"""Compiles that guard the chip: the Pallas kernels and the pinned-host
expert fetch, at Mixtral-8x7B widths (DeepSeek-V3 widths for the MLA
kernel), compiled by the TPU compiler for a described v5e chip.

Interpret-mode tests cannot see what the TPU compiler refuses — a block
whose last two dims are neither (8, 128)-divisible nor the array's, a
rank-1 VMEM block, a gather on a host-memory operand — so each case here
lowers with ``interpret=False`` and compiles.  Nothing runs.  (The
grouped MoE-FFN kernel is absent: its (D, 2, F) gate/up weight tile pads
the size-2 axis to a full sublane tile and overflows the 16 MiB scoped
VMEM at any F block.)  The topology is described inside a module
fixture (never at import: only one process at a time may load the TPU
library), and the persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back
without one)."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import paging
from repro.kernels import flash_prefill as _flash
from repro.kernels import gqa_decode as _gqa
from repro.kernels import paged_decode as _paged
from repro.models.model import _ExpertCtx

# Mixtral-8x7B decode at the serving engine's default paged-KV settings
B, H, HKV, D, BT = 8, 32, 8, 128, 16
MB = 256 // BT                      # blocks per slot at max_seq 256
NB = B * MB + 1                     # half of two slot groups' blocks + trash
D_MODEL, D_FF, E, TOP_K = 4096, 14336, 8, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, in_shardings=None):
    kw = {} if in_shardings is None else dict(in_shardings=in_shardings)
    return jax.jit(fn, **kw).lower(*args).compile()


def _assert_kernel(compiled, name):
    """The kernel is in the program, under its stable name: the custom
    call's instruction is what the device trace shows."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call", text), name


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_paged_gqa_decode_compiles(one_chip, fused, int8):
    s = functools.partial(_shape, one_chip)
    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    args = [s((B, H, D), jnp.bfloat16), s((HKV, NB, BT, D), kv_dt),
            s((HKV, NB, BT, D), kv_dt), s((NB, BT), jnp.int32),
            s((B, MB), jnp.int32), s((B,), jnp.int32)]
    names = []
    if int8:
        args += [s((HKV, NB, BT), jnp.float32)] * 2
        names += ["k_scale", "v_scale"]
    if fused:
        args += [s((B, HKV, D), kv_dt)] * 2
        names += ["k_new", "v_new"]
        if int8:
            args += [s((B, HKV), jnp.float32)] * 2
            names += ["k_scale_new", "v_scale_new"]

    def fn(q, k, v, sp, pt, pos, *extra):
        return _paged.paged_gqa_decode(q, k, v, sp, pt, pos, scale=D ** -0.5,
                                       interpret=False,
                                       **dict(zip(names, extra)))

    _assert_kernel(_compile(fn, *args), "paged_gqa_decode")


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_paged_mla_decode_compiles(one_chip, fused):
    # DeepSeek-V3 absorbed MLA: 128 heads, latent 512, rope 64
    s = functools.partial(_shape, one_chip)
    h, lat, dr = 128, 512, 64
    args = [s((B, h, lat + dr), jnp.bfloat16), s((NB, BT, lat), jnp.bfloat16),
            s((NB, BT, dr), jnp.bfloat16), s((NB, BT), jnp.int32),
            s((B, MB), jnp.int32), s((B,), jnp.int32)]
    if fused:
        args += [s((B, lat), jnp.bfloat16), s((B, dr), jnp.bfloat16)]

    def fn(qcat, ckv, kr, sp, pt, pos, *new):
        kw = dict(zip(("ckv_new", "kr_new"), new))
        return _paged.paged_mla_decode(qcat, ckv, kr, sp, pt, pos,
                                       scale=(lat + dr) ** -0.5, lat=lat,
                                       interpret=False, **kw)

    _assert_kernel(_compile(fn, *args), "paged_mla_decode")


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_gqa_decode_compiles(one_chip, int8):
    s = functools.partial(_shape, one_chip)
    W = 512
    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    args = [s((B, H, D), jnp.bfloat16), s((B, W, HKV, D), kv_dt),
            s((B, W, HKV, D), kv_dt), s((B, W), jnp.bool_)]
    if int8:
        args += [s((B, W, HKV), jnp.float32)] * 2

    def fn(q, k, v, valid, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return _gqa.gqa_decode(q, k, v, valid, scale=D ** -0.5,
                               interpret=False, **kw)

    _assert_kernel(_compile(fn, *args), "gqa_decode")


def test_flash_prefill_compiles(one_chip):
    s = functools.partial(_shape, one_chip)
    S = 256
    args = [s((1, S, H, D), jnp.bfloat16), s((1, S, HKV, D), jnp.bfloat16),
            s((1, S, HKV, D), jnp.bfloat16), s((1,), jnp.int32)]

    def fn(q, k, v, kv_len):
        return _flash.flash_prefill(q, k, v, kv_len=kv_len, interpret=False)

    _assert_kernel(_compile(fn, *args), "flash_prefill")


def test_expert_fetch_from_pinned_host_store(one_chip):
    """One paged MoE step at decode width: the router-gated fetch moves
    only the activated experts' spans and applies each from the layout it
    is stored in.  The store is host argument bytes, and the device temp
    bytes stay below two spans: one expert's weights are live at a time,
    with no stacked subset and no transposed copy."""
    from repro.configs import get_config
    from repro.models import moe

    host = one_chip.with_memory_kind("pinned_host")
    cfg = get_config("mixtral-8x7b")
    L = 2
    leaves = [(("moe", "wi"), jax.ShapeDtypeStruct(
                  (L, E, D_MODEL, 2, D_FF), jnp.bfloat16)),
              (("moe", "wo"), jax.ShapeDtypeStruct(
                  (L, E, D_FF, D_MODEL), jnp.bfloat16))]
    em = paging.expert_manifest(leaves)
    assert em.span_shape == (3, D_MODEL, D_FF)
    slots = 4
    store = jax.core.ShapedArray((L, E) + em.span_shape, jnp.bfloat16,
                                 memory_space=jax.memory.Space.Host)
    args = (store,
            _shape(one_chip, (slots,) + em.span_shape, jnp.bfloat16),
            _shape(one_chip, (L, E), jnp.int32),
            _shape(one_chip, (), jnp.int32),
            _shape(one_chip, (D_MODEL, E), jnp.bfloat16),
            _shape(one_chip, (B, D_MODEL), jnp.bfloat16))

    def fn(pages, pool, resident_map, layer, router, x):
        fetch = _ExpertCtx(pages, em, pool, resident_map).make_fetch(layer)
        return moe.moe_paged(cfg, {"router": router}, x,
                             fetch_experts=fetch)

    compiled = _compile(fn, *args, in_shardings=(host,) + (one_chip,) * 5)
    ma = compiled.memory_analysis()
    assert ma.host_argument_size_in_bytes == L * E * em.span_bytes
    assert ma.temp_size_in_bytes < 2 * em.span_bytes
