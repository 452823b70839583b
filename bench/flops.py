"""Operations and bytes of one kernel call or one token, from shapes.

These are what the algorithm needs for the call as it is made (every
row the call is given, every weight read once), not what a kernel
happens to move. ``least_time`` is the chip's floor for them: the
larger of operations over peak FLOP/s and bytes over peak bandwidth.
"""
from __future__ import annotations


def moe_ffn_call(u: int, rows: int, d: int, f: int, *, w_bytes: int = 2,
                 x_bytes: int = 2, out_bytes: int = 4):
    """Grouped SwiGLU over ``u`` experts, each given all ``rows`` rows:
    x [u, rows, d]; w1, w3 [u, d, f]; w2 [u, f, d] -> fp32 [u, rows, d].
    Returns (flops, bytes)."""
    flops = 3 * 2 * u * rows * d * f
    nbytes = (3 * u * d * f * w_bytes + u * rows * d * x_bytes
              + u * rows * d * out_bytes)
    return flops, nbytes


def paged_attention_call(rows: int, blocks: int, block_size: int, heads: int,
                         kv_heads: int, head_dim: int, *, kv_bytes: int = 2,
                         q_bytes: int = 2):
    """Single-token decode attention of ``rows`` query rows, each over
    ``blocks`` KV blocks of ``block_size`` positions (its table row).
    Returns (flops, bytes)."""
    keys = rows * blocks * block_size
    flops = 2 * 2 * heads * head_dim * keys
    nbytes = (2 * keys * kv_heads * head_dim * kv_bytes
              + 2 * rows * heads * head_dim * q_bytes + 4 * rows * blocks)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) — the floor of a call on one chip and which
    peak sets it ("flops" or "bytes")."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def gqa_moe_token_flops(d: int, heads: int, kv_heads: int, head_dim: int,
                        ffn: int, experts: int, top_k: int, vocab: int,
                        layers: int, context: float) -> float:
    """Model FLOPs of one token through a GQA decoder whose every layer
    is a top-k SwiGLU mixture: projections, attention over ``context``
    earlier positions, router, the k experts it routes to, and the
    output head."""
    proj = 2 * d * head_dim * (2 * heads + 2 * kv_heads)
    attend = 2 * 2 * heads * head_dim * context
    moe = 2 * d * experts + top_k * 3 * 2 * d * ffn
    return layers * (proj + attend + moe) + 2 * d * vocab
