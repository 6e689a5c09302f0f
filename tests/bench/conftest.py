import json
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny-moe", "source": "test stand-in at small widths",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 512, "num_local_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000.0, "reference": "moe_gqa",
    "run": {"arch": "mixtral-8x7b",
            "overrides": {"num_layers": 2, "norm_eps": 1e-5, "d_model": 64,
                          "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                          "d_ff": 128, "vocab_size": 512},
            "matches": {"d_model": "hidden_size",
                        "num_heads": "num_attention_heads",
                        "num_kv_heads": "num_key_value_heads",
                        "d_ff": "intermediate_size",
                        "vocab_size": "vocab_size",
                        "num_experts": "num_local_experts",
                        "top_k": "num_experts_per_tok",
                        "num_layers": "num_hidden_layers",
                        "norm_eps": "rms_norm_eps"}},
}
TINY_TRAFFIC = {
    "name": "tiny", "loop": "closed", "queue_slots": 1,
    "pool": {"size": 64, "seed": 5},
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.4,
                   "min": 8, "max": 32},
    "output_len": {"dist": "uniform", "min": 4, "max": 12},
}
TINY_CELL = {
    "engine": {"ubatch": 2, "num_ubs": 2, "max_seq": 64,
               "expert_paged": True, "w_gpu_ratio": 0.125,
               "kv_paged": True, "kv_gpu_ratio": 0.25},
    "policy": {"paged_attn_impl": "pallas"},
    "check": {"sample_tokens": 24, "max_requests": 4,
              "limits": {"mean_gap": 0.005}},
}


def make_root(tmp: Path, *, extra_metric: str = None) -> Path:
    """A checkout-shaped directory for the tiny cell: its own
    BENCHMARK.json, config, traffic and cell files, the real reference
    modules, metric readers and program."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    root = tmp / "root"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "cells").mkdir()
    (root / "bench" / "references").mkdir()
    for ref in (REPO / "bench" / "references").glob("*.py"):
        os.symlink(ref, root / "bench" / "references" / ref.name)
    os.symlink(REPO / "bench" / "metrics", root / "bench" / "metrics")
    os.symlink(REPO / "src", root / "src")
    (root / "bench" / "configs" / "tiny-moe.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "bench" / "cells" / "tiny-moe.offload.tiny.json").write_text(
        json.dumps(TINY_CELL))
    bench["configs"] = [{"name": "tiny-moe", "source": "test",
                         "file": "bench/configs/tiny-moe.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-moe.offload.tiny",
                           "config": "tiny-moe", "traffic": "tiny",
                           "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
