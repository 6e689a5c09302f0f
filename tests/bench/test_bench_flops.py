import json

import pytest

from conftest import REPO

from bench import flops
from bench.references import moe_gqa

MIXTRAL = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 8,
           "head_dim": 128, "d_ff": 14336, "vocab_size": 32000,
           "num_experts": 8, "top_k": 2, "num_layers": 2}


def test_layer_flops_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096: 41,943,040 MACs;
    # router 4096x8; two experts of 3 x 4096 x 14336; attention over 100
    # positions: 2 x 32 heads x 128 x 100 MACs
    macs = 41_943_040 + 32_768 + 2 * 3 * 4096 * 14336 + 2 * 32 * 128 * 100
    assert moe_gqa.layer_flops(MIXTRAL, 100) == 2 * macs


def test_decode_and_prefill_flops():
    head = 2 * 4096 * 32000
    assert moe_gqa.decode_flops(MIXTRAL, 100) == \
        2 * moe_gqa.layer_flops(MIXTRAL, 100) + head
    # a 3-token prompt: positions see 1, 2 and 3 positions
    want = 2 * (3 * moe_gqa.layer_flops(MIXTRAL, 0)
                + 4 * 32 * 128 * (1 + 2 + 3)) + head
    assert moe_gqa.prefill_flops(MIXTRAL, 3) == want


def test_paged_decode_work_by_hand():
    f, b = moe_gqa.paged_decode_work(MIXTRAL, 200)
    assert f == 4 * 32 * 128 * 200
    # K and V: 200 positions x 8 heads x 128 x 2 bytes each; q and out
    assert b == 2 * 200 * 8 * 128 * 2 + 2 * 32 * 128 * 2


def test_roofline_takes_the_slower_bound():
    peak = json.loads((REPO / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]
    assert flops.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(197e12, 2 * 819e9, peak) == \
        pytest.approx(2.0)


def test_decode_kernel_work_counts_every_layer():
    f, b = moe_gqa.paged_decode_work(MIXTRAL, 200)
    assert moe_gqa.decode_kernel_work(MIXTRAL, 200) == (2 * f, 2 * b)
