"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs count one multiply-add as 2 operations and take only what a
token needs: its projections, its router, its top-k experts, attention
over the context it sees, and the output head for a generated token.
The paged-decode kernel's least work is one query row per sequence
against the K and V of that sequence's live context.
"""
from __future__ import annotations


def layer_flops(d: dict, ctx: int) -> int:
    """One token through one layer, attending over ``ctx`` positions."""
    D, H, Hkv, Dh = d["d_model"], d["num_heads"], d["num_kv_heads"], \
        d["head_dim"]
    proj = 2 * D * (H * Dh + 2 * Hkv * Dh) + 2 * H * Dh * D
    attn = 4 * H * Dh * ctx                      # q.k and p.v
    router = 2 * D * d["num_experts"]
    experts = d["top_k"] * 2 * 3 * D * d["d_ff"]
    return proj + attn + router + experts


def head_flops(d: dict) -> int:
    return 2 * d["d_model"] * d["vocab_size"]


def prefill_flops(d: dict, prompt_len: int) -> int:
    """A prompt, causal (position i sees i + 1 positions), and the head
    at its last position."""
    n = prompt_len
    per_layer = n * layer_flops(d, 0) + 4 * d["num_heads"] * d["head_dim"] \
        * n * (n + 1) // 2
    return d["num_layers"] * per_layer + head_flops(d)


def decode_flops(d: dict, ctx: int) -> int:
    """One generated token whose query sees ``ctx`` positions."""
    return d["num_layers"] * layer_flops(d, ctx) + head_flops(d)


def paged_decode_work(d: dict, ctx: int, kv_bytes: int = 2):
    """(flops, bytes) of one sequence's decode attention in one layer:
    read its q, the K and V of ``ctx`` positions, write its output."""
    H, Hkv, Dh = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    flops = 4 * H * Dh * ctx
    nbytes = 2 * ctx * Hkv * Dh * kv_bytes + 2 * H * Dh * kv_bytes
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the slower of compute and memory."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
