"""Attention variants: GQA (full/blockwise + KV-cache decode), sliding
window, MLA (DeepSeek-V2, with the absorbed decode path over the
compressed latent), and cross-attention (enc-dec / VLM).

Conventions
-----------
* Full-sequence paths take ``x [B, S, d]`` and scalar/vector positions.
* Decode paths take ``x [B, 1, d]``, a cache pytree and scalar ``pos``
  (position of the incoming token; the same for every sequence in the
  batch — continuous batching with ragged positions lives in
  ``repro.serving`` on top of this).
* Sliding-window decode uses a ring buffer of size ``window``; keys are
  RoPE'd at their absolute position when written.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, dense_init, rope_cos_sin
from repro.models.sharding import constrain, padded_count

NEG_INF = -1e30

# Full-sequence attention implementation for GQA/MLA/cross paths:
# "xla_blockwise" (CPU/dry-run default) | "pallas" (TPU) |
# "pallas_interpret" (kernel body on CPU — tests). The Pallas kernel
# supports MLA's narrower V width (hd) vs QK width (hd+rd).
ATTN_IMPL = "xla_blockwise"


def _head_padding(H: int, KV: int):
    """Padded (Hp, KVp) for even model-axis sharding (see
    sharding.padded_count). KV pads to Hp when grouping breaks (MHA)."""
    Hp = padded_count(H)
    KVp = KV if Hp % KV == 0 and (Hp // KV) * KV == Hp else Hp
    if Hp % KVp != 0:
        KVp = Hp
    return Hp, KVp


def _pad_heads(w, target: int, axis: int):
    if w.shape[axis] == target:
        return w
    widths = [(0, 0)] * w.ndim
    widths[axis] = (0, target - w.shape[axis])
    return jnp.pad(w, widths)


# =====================================================================
# init
# =====================================================================
def init_gqa(key, cfg, dtype, *, kv_heads: Optional[int] = None):
    d, H = cfg.d_model, cfg.num_heads
    kv = cfg.num_kv_heads if kv_heads is None else kv_heads
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    res_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), d, dtype=dtype),
        "wk": dense_init(ks[1], (d, kv, hd), d, dtype=dtype),
        "wv": dense_init(ks[2], (d, kv, hd), d, dtype=dtype),
        "wo": dense_init(ks[3], (H, hd, d), H * hd, scale=res_scale, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    return p


def init_mla(key, cfg, dtype):
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    r, rd, rq = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.q_lora_rank
    ks = jax.random.split(key, 6)
    res_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    p = {
        "w_dkv": dense_init(ks[1], (d, r), d, dtype=dtype),
        "w_kr": dense_init(ks[2], (d, rd), d, dtype=dtype),
        "latent_norm": jnp.ones((r,), dtype),
        "w_kb": dense_init(ks[3], (r, H, hd), r, dtype=dtype),
        "w_vb": dense_init(ks[4], (r, H, hd), r, dtype=dtype),
        "wo": dense_init(ks[5], (H, hd, d), H * hd, scale=res_scale, dtype=dtype),
    }
    if rq:  # query LoRA: x @ w_qa, RMSNorm, @ w_qb
        ka, kb = jax.random.split(ks[0])
        p["w_qa"] = dense_init(ka, (d, rq), d, dtype=dtype)
        p["q_norm"] = jnp.ones((rq,), dtype)
        p["w_qb"] = dense_init(kb, (rq, H, hd + rd), rq, dtype=dtype)
    else:
        p["wq"] = dense_init(ks[0], (d, H, hd + rd), d, dtype=dtype)
    return p


def init_attention(key, cfg, dtype):
    return init_mla(key, cfg, dtype) if cfg.use_mla else init_gqa(key, cfg, dtype)


def init_cross_attention(key, cfg, dtype):
    # Cross-attention is MHA (kv heads == q heads) over the frontend states.
    return init_gqa(key, cfg, dtype, kv_heads=cfg.num_heads)


# =====================================================================
# helpers
# =====================================================================
def _project_qkv(p, cfg, x, positions, *, rope: bool):
    """x [B,S,d] -> q [B,S,Hp,hd], k/v [B,S,KVp,hd] (roped if requested).

    Head counts are zero-padded up to the model-axis size so attention
    shards instead of replicating (exact: wo's padded rows are zero —
    §Perf measured 16x redundant attention compute for 40-head archs on
    a 16-way axis without this)."""
    H = p["wq"].shape[1]
    KV = p["wk"].shape[1]
    Hp, KVp = _head_padding(H, KV)
    wq = _pad_heads(p["wq"], Hp, 1)
    wk = _pad_heads(p["wk"], KVp, 1)
    wv = _pad_heads(p["wv"], KVp, 1)
    # constrain() drops the axis when the dim doesn't divide (e.g. a
    # 2-kv-head GQA cache stays replicated while 48 padded q-heads shard)
    wq = constrain(wq, None, "heads", None)
    wk = constrain(wk, None, "heads", None)
    wv = constrain(wv, None, "heads", None)
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if "bq" in p:
        q = q + _pad_heads(p["bq"], Hp, 0)
        k = k + _pad_heads(p["bk"], KVp, 0)
        v = v + _pad_heads(p["bv"], KVp, 0)
    if rope and cfg.pos_emb == "rope":
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]  # [B,S,1,hd/2]
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "heads", None)
    v = constrain(v, "batch", None, "heads", None)
    return q, k, v


def _axis_size() -> int:
    from repro.models.sharding import active_mesh, active_rules
    mesh = active_mesh()
    m = active_rules().get("model") if mesh is not None else None
    return mesh.shape[m] if m else 1


def _sdpa_blockwise(q, k, v, *, causal: bool, window: Optional[int],
                    q_offset, block_q: int = 512, block_k: int = 512,
                    scale: Optional[float] = None):
    """Online-softmax blockwise attention (flash-attention schedule in XLA).

    q [B,Sq,H,hd]; k/v [B,Sk,KV,hd]; GQA broadcast H over KV.
    q_offset: absolute position of q[0] minus that of k[0] (for causal
    masks when Sq != Sk).  Returns [B,Sq,H,hd].
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    vd = v.shape[-1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    pad_q = nq * block_q - Sq
    pad_k = nk * block_k - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qb = q.reshape(B, nq, block_q, KV, G, hd)
    kb = k.reshape(B, nk, block_k, KV, hd)
    vb = v.reshape(B, nk, block_k, KV, vd)

    q_pos = q_offset + jnp.arange(nq * block_q).reshape(nq, block_q)
    k_pos = jnp.arange(nk * block_k).reshape(nk, block_k)
    k_valid = (jnp.arange(nk * block_k) < Sk).reshape(nk, block_k)

    def q_block(qi, qblk, qpos):
        # qblk [B, block_q, KV, G, hd]; qpos [block_q]
        def kv_step(carry, xs):
            m, l, acc = carry
            kblk, vblk, kpos, kval = xs
            # native-dtype operands, fp32 accumulation (MXU pattern)
            s = jnp.einsum("bqkgh,bskh->bqkgs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = kval[None, None, None, None, :]
            if causal:
                mask = mask & (kpos[None, None, None, None, :]
                               <= qpos[None, :, None, None, None])
            if window is not None:
                mask = mask & (qpos[None, :, None, None, None]
                               - kpos[None, None, None, None, :] < window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgs,bskh->bqkgh", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, block_q, KV, G), NEG_INF, jnp.float32),
                jnp.zeros((B, block_q, KV, G), jnp.float32),
                jnp.zeros((B, block_q, KV, G, vd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (kb.transpose(1, 0, 2, 3, 4),
                                                      vb.transpose(1, 0, 2, 3, 4),
                                                      k_pos, k_valid))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(lambda xs: q_block(*xs),
                      (jnp.arange(nq), qb.transpose(1, 0, 2, 3, 4, 5), q_pos))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * block_q, H, vd)
    return out[:, :Sq].astype(v.dtype)


# =====================================================================
# GQA full-sequence (train / prefill)
# =====================================================================
def _sdpa(q, k, v, *, causal: bool, window: Optional[int]):
    """Dispatch full-seq attention to XLA blockwise or the Pallas
    flash kernel per ``ATTN_IMPL``."""
    if ATTN_IMPL == "xla_blockwise":
        return _sdpa_blockwise(q, k, v, causal=causal, window=window,
                               q_offset=0)
    from repro.kernels import ops as kops
    return kops.flash_attention(q, k, v, causal=causal,
                                window=window or 0, impl=ATTN_IMPL)


def gqa_full(p, cfg, x, positions, *, window: Optional[int] = None,
             causal: bool = True):
    """x [B,S,d], positions [B,S] -> [B,S,d]."""
    q, k, v = _project_qkv(p, cfg, x, positions, rope=True)
    out = _sdpa(q, k, v, causal=causal, window=window)
    wo = _pad_heads(p["wo"], q.shape[2], 0)  # padded rows are zero: exact
    return jnp.einsum("bshk,hkd->bsd", out, wo)


# =====================================================================
# GQA decode with KV cache (full or ring/sliding window)
# =====================================================================
def gqa_cache_init(cfg, batch: int, cache_len: int, dtype):
    _, kv = _head_padding(cfg.num_heads, cfg.num_kv_heads)
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
    }


def gqa_decode(p, cfg, x, cache, pos, *, window: Optional[int] = None):
    """x [B,1,d]; cache {k,v [B,L,kv,hd]}; pos scalar int32."""
    B = x.shape[0]
    if window is None:
        # full cache: one shared core with the continuous-batching path
        return gqa_decode_multipos(p, cfg, x, cache,
                                   jnp.full((B,), pos, jnp.int32))
    L = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions, rope=True)

    slot = pos % L
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))

    H, KV, hd = q.shape[2], k.shape[2], cfg.head_dim
    G = H // KV
    # bf16 operands + fp32 accumulation (MXU-native); never up-cast the
    # cache — converting [B,L,kv,hd] to f32 per step dominated decode
    # HBM traffic in the baseline (EXPERIMENTS.md §Perf).
    qf = q.reshape(B, KV, G, hd).astype(k.dtype)
    s = jnp.einsum("bkgh,blkh->bkgl", qf, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)

    idx = jnp.arange(L)
    # slot i holds absolute position p_i = pos - ((pos - i) mod L)
    p_i = pos - jnp.mod(pos - idx, L)
    valid = p_i >= 0
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, H, hd).astype(x.dtype)
    wo = _pad_heads(p["wo"], H, 0)
    y = jnp.einsum("bshk,hkd->bsd", out, wo)
    return y, {"k": k, "v": v}


def gqa_decode_multipos(p, cfg, x, cache, pos_vec):
    """Decode with a PER-ROW position vector (continuous batching).

    x [B,1,d]; cache {k,v [B,L,kv,hd]}; pos_vec [B] int32 — row b writes
    its K/V at slot pos_vec[b] and attends to slots <= pos_vec[b]. This
    is also the shared full-cache core of ``gqa_decode`` (which passes a
    broadcast scalar position), so single-stream and batched serving
    stay bit-compatible by construction. Sliding windows are not
    supported here (ring-buffer slots need the scalar-pos path).
    bf16 operands + fp32 accumulation; the cache is never up-cast (the
    per-step f32 convert dominated decode HBM traffic — EXPERIMENTS.md).
    """
    q, k_new, v_new = gqa_decode_qkv(p, cfg, x, pos_vec)
    cache = gqa_append(cache, k_new, v_new, dense_cells(pos_vec))
    out = gqa_attend(q, cache["k"], cache["v"], pos_vec)
    return gqa_out(p["wo"], out, x.dtype), cache


# The pieces of one decode step, shared by the dense and paged layouts
# (and jitted piecewise by ``repro.models.transformer``).
def gqa_decode_qkv(p, cfg, x, pos_vec):
    """x [B,1,d], pos_vec [B] -> q [B,Hp,hd] and the new k/v [B,KVp,hd],
    roped at each row's position."""
    positions = jnp.reshape(pos_vec, (x.shape[0], 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions, rope=True)
    return q[:, 0], k[:, 0], v[:, 0]


def dense_cells(pos_vec):
    """Dense-cache cell of each row's new K/V: (row, pos)."""
    return jnp.arange(pos_vec.shape[0]), pos_vec.astype(jnp.int32)


def paged_cells(block_tables, pos_vec, block_size: int):
    """Pool cell of each row's new K/V: (table[pos // bs], pos % bs).
    Tables of live requests never alias (allocator invariant), so rows
    write disjoint cells."""
    pos = pos_vec.astype(jnp.int32)
    blk = block_tables[jnp.arange(pos.shape[0]), pos // block_size]
    return blk, pos % block_size


def gqa_append(cache, k_new, v_new, cells):
    """Scatter row b's k/v [B,KV,hd] into ``cache`` at ``cells`` (an
    in-place XLA scatter, not a full-cache select)."""
    return {"k": cache["k"].at[cells].set(k_new.astype(cache["k"].dtype)),
            "v": cache["v"].at[cells].set(v_new.astype(cache["v"].dtype))}


def gqa_attend(q, k, v, pos_vec):
    """q [B,H,hd] over each row's logical K/V strip k/v [B,L,KV,hd],
    keys at index <= pos_vec[b] -> [B,H,hd] fp32. bf16 operands, fp32
    accumulation; the strip is never up-cast."""
    B, H, hd = q.shape
    L, KV = k.shape[1], k.shape[2]
    qf = q.reshape(B, KV, H // KV, hd).astype(k.dtype)
    s = jnp.einsum("bkgh,blkh->bkgl", qf, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)
    valid = jnp.arange(L)[None, :] <= pos_vec[:, None]  # [B, L]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd)


def gqa_attend_paged(q, cache, block_tables, pos_vec):
    """``gqa_attend`` over the strip each row's table gathers from the
    pool: [B,T,bs,kv,hd] -> [B,T*bs,kv,hd]."""
    B, T = block_tables.shape
    k, v = cache["k"], cache["v"]
    kg = k[block_tables].reshape(B, T * k.shape[1], *k.shape[2:])
    vg = v[block_tables].reshape(B, T * v.shape[1], *v.shape[2:])
    return gqa_attend(q, kg, vg, pos_vec)


def gqa_out(wo, out, dtype):
    """Heads' output [B,H,hd] -> [B,1,d] through ``wo`` (its zero-padded
    head rows match q's padding)."""
    out = out[:, None].astype(dtype)
    return jnp.einsum("bshk,hkd->bsd", out, _pad_heads(wo, out.shape[2], 0))


# =====================================================================
# GQA paged decode (block-table KV — continuous serving over a pool)
# =====================================================================
def gqa_paged_cache_init(cfg, num_blocks: int, block_size: int, dtype):
    """One layer's K/V block pool: [N, bs, kv, hd] (vs dense [B, L, kv, hd])."""
    _, kv = _head_padding(cfg.num_heads, cfg.num_kv_heads)
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        "v": jnp.zeros((num_blocks, block_size, kv, hd), dtype),
    }


def gqa_decode_paged(p, cfg, x, cache, pos_vec, block_tables):
    """``gqa_decode_multipos`` reading K/V through a block table.

    x [B,1,d]; cache {k,v [N,bs,kv,hd]} (the shared pool); pos_vec [B]
    request-LOCAL positions; block_tables [B,T] int32 — logical block i
    of row b lives at physical block ``block_tables[b, i]``. Row b's new
    K/V is scattered to (table[pos//bs], pos%bs); attention gathers the
    row's T blocks back into a [T*bs] logical strip and masks
    ``idx <= pos`` exactly like the dense path, so paged and dense
    decode are BIT-IDENTICAL: gathered keys occupy the same logical
    indices, masked lanes underflow to exactly zero weight, and zero
    rows are exact no-ops in the fp32 accumulation (test-enforced
    token-for-token equality). Padded/stale table entries are
    unreachable for the same reason.

    Multi-position append (chunked prefill) contract: several rows MAY
    share one request's table, at DISTINCT consecutive positions —
    their (block, offset) scatter cells are then distinct, every
    scatter lands before any gather reads the pool, and the causal
    mask keeps row j blind to positions > pos_vec[j]. A chunk of N
    known tokens fed as N such "virtual rows" in one call is therefore
    bit-exact with N single-token calls (test-enforced, see
    ``OffloadEngine.prefill_tokens``). Two rows at the SAME (block,
    offset) remain undefined — callers must never duplicate positions
    within a request.
    """
    q, k_new, v_new = gqa_decode_qkv(p, cfg, x, pos_vec)
    cache = gqa_append(cache, k_new, v_new,
                       paged_cells(block_tables, pos_vec, cache["k"].shape[1]))
    if PAGED_ATTN_IMPL == "xla":
        out = gqa_attend_paged(q, cache, block_tables, pos_vec)
    else:
        from repro.kernels import ops as kops
        out = kops.paged_attention(q, cache["k"], cache["v"], block_tables,
                                   pos_vec, impl=PAGED_ATTN_IMPL)
    return gqa_out(p["wo"], out, x.dtype), cache


# Paged decode attention implementation: "xla" (gather + masked softmax,
# bit-identical to the dense multipos path — CPU/test default) |
# "pallas" (TPU block-table gather kernel) | "pallas_interpret".
PAGED_ATTN_IMPL = "xla"


# =====================================================================
# MLA (DeepSeek-V2)
# =====================================================================
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def mla_inv_freq(cfg) -> np.ndarray:
    """The rope key's inverse frequencies [qk_rope_dim / 2]: θ^(-2i/dim),
    or under YaRN ``rope_scaling`` (DeepSeek-V2's modelling code) those
    and the same divided by ``factor``, blended by a linear ramp between
    the floor and the ceiling of the correction dims of ``beta_fast``
    and ``beta_slow`` at ``original_max_position_embeddings``: the
    fastest dims keep θ^(-2i/dim), the slowest are interpolated."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ys = dict(cfg.rope_scaling)
    if not ys:
        return extra.astype(np.float32)
    orig = ys["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    lo = max(math.floor(corr(ys["beta_fast"])), 0)
    hi = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    inter = extra / ys["factor"]
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def mla_softmax_mscale(cfg) -> float:
    """YaRN's factor on the softmax scale (hd + rd)^-0.5: mscale² of
    ``mscale_all_dim`` (1.0 without ``rope_scaling``)."""
    ys = dict(cfg.rope_scaling)
    if not ys.get("mscale_all_dim"):
        return 1.0
    return _yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2


def _mla_rope(cfg, positions):
    """cos, sin [..., rd/2] of the rope key at ``positions``."""
    ys = dict(cfg.rope_scaling)
    if not ys:
        return rope_cos_sin(positions, cfg.qk_rope_dim, cfg.rope_theta)
    ang = positions[..., None].astype(jnp.float32) * mla_inv_freq(cfg)
    m = (_yarn_mscale(ys["factor"], ys.get("mscale", 1.0))
         / _yarn_mscale(ys["factor"], ys.get("mscale_all_dim", 1.0)))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _mla_q(p, cfg, x, positions):
    from repro.models.layers import rms_norm
    hd = cfg.head_dim
    if "w_qa" in p:
        qa = rms_norm(x @ p["w_qa"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", qa, p["w_qb"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    cos, sin = _mla_rope(cfg, positions)
    q_rope = apply_rope(q_rope, cos[:, :, None, :], sin[:, :, None, :])
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    from repro.models.layers import rms_norm
    latent = rms_norm(x @ p["w_dkv"], p["latent_norm"], cfg.norm_eps)
    k_rope = x @ p["w_kr"]  # [B,S,rd], shared across heads
    cos, sin = _mla_rope(cfg, positions)
    k_rope = apply_rope(k_rope[:, :, None, :], cos[:, :, None, :],
                        sin[:, :, None, :])[:, :, 0, :]
    return latent, k_rope


def mla_full(p, cfg, x, positions, *, window: Optional[int] = None,
             causal: bool = True):
    """Training/prefill path: materialise per-head K/V from the latent."""
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    latent, k_rope = _mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", latent, p["w_kb"])
    v = jnp.einsum("bsr,rhk->bshk", latent, p["w_vb"])
    H = cfg.num_heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_rope.shape[:2], H, cfg.qk_rope_dim))],
        axis=-1)
    # the default scale 1/sqrt(q.shape[-1]) IS 1/sqrt(hd + rd) here;
    # YaRN's mscale² rides on q
    m2 = mla_softmax_mscale(cfg)
    if m2 != 1.0:
        q = q * m2
    out = _sdpa(q, k, v, causal=causal, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _mla_scale(s, cfg):
    """Scores times the softmax scale (hd + rd)^-0.5, with YaRN's
    mscale² where the config has it."""
    s = s / math.sqrt(cfg.head_dim + cfg.qk_rope_dim)
    m2 = mla_softmax_mscale(cfg)
    return s * m2 if m2 != 1.0 else s


def mla_cache_init(cfg, batch: int, cache_len: int, dtype):
    return {
        "latent": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(p, cfg, x, cache, pos, *, window: Optional[int] = None):
    """Absorbed decode: attention runs in the r-dim latent space.

    Cache stores only [B,L,r] latents + [B,L,rd] rope keys — the MLA
    memory win. q_nope is absorbed through w_kb; attention output in
    latent space is expanded through w_vb.
    """
    B = x.shape[0]
    if window is None:
        # full cache: one shared core with the continuous-batching path
        return mla_decode_multipos(p, cfg, x, cache,
                                   jnp.full((B,), pos, jnp.int32))
    L = cache["latent"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)       # [B,1,H,hd],[B,1,H,rd]
    latent_new, k_rope_new = _mla_latent(p, cfg, x, positions)

    slot = pos % L
    latent = jax.lax.dynamic_update_slice(
        cache["latent"], latent_new.astype(cache["latent"].dtype), (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), (0, slot, 0))

    # absorb: q_abs [B,H,r]. bf16 operands + fp32 accumulation; the
    # latent cache is never up-cast (see §Perf — the f32 convert of the
    # whole cache per layer was the baseline's dominant traffic).
    cdt = cache["latent"].dtype
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_kb"],
                       preferred_element_type=jnp.float32)
    s = jnp.einsum("bhr,blr->bhl", q_abs.astype(cdt), latent,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,blk->bhl", q_rope[:, 0].astype(cdt), k_rope,
                       preferred_element_type=jnp.float32)
    s = _mla_scale(s, cfg)

    idx = jnp.arange(L)
    p_i = pos - jnp.mod(pos - idx, L)
    valid = p_i >= 0
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhl,blr->bhr", w.astype(cdt), latent,
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("bhr,rhk->bhk", ctx.astype(p["w_vb"].dtype), p["w_vb"],
                     preferred_element_type=jnp.float32)
    out = out[:, None].astype(x.dtype)  # [B,1,H,hd]
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"latent": latent, "k_rope": k_rope}


def mla_decode_multipos(p, cfg, x, cache, pos_vec):
    """Absorbed MLA decode with a per-row position vector [B] (see
    ``gqa_decode_multipos`` for the contract). Also the shared
    full-cache core of ``mla_decode``; windows stay on the scalar-pos
    ring-buffer path."""
    B = x.shape[0]
    L = cache["latent"].shape[1]
    positions = jnp.reshape(pos_vec, (B, 1)).astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    latent_new, k_rope_new = _mla_latent(p, cfg, x, positions)

    rows = jnp.arange(B)
    latent = cache["latent"].at[rows, positions[:, 0]].set(
        latent_new[:, 0].astype(cache["latent"].dtype))
    k_rope = cache["k_rope"].at[rows, positions[:, 0]].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype))

    cdt = cache["latent"].dtype
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_kb"],
                       preferred_element_type=jnp.float32)
    s = jnp.einsum("bhr,blr->bhl", q_abs.astype(cdt), latent,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,blk->bhl", q_rope[:, 0].astype(cdt), k_rope,
                       preferred_element_type=jnp.float32)
    s = _mla_scale(s, cfg)

    valid = jnp.arange(L)[None, :] <= positions  # [B, L]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhl,blr->bhr", w.astype(cdt), latent,
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("bhr,rhk->bhk", ctx.astype(p["w_vb"].dtype), p["w_vb"],
                     preferred_element_type=jnp.float32)
    out = out[:, None].astype(x.dtype)  # [B,1,H,hd]
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"latent": latent, "k_rope": k_rope}


# =====================================================================
# MLA paged decode (block-table latent pool)
# =====================================================================
def mla_paged_cache_init(cfg, num_blocks: int, block_size: int, dtype):
    """One layer's latent block pool: [N, bs, r] + [N, bs, rd]."""
    return {
        "latent": jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((num_blocks, block_size, cfg.qk_rope_dim), dtype),
    }


def mla_decode_paged(p, cfg, x, cache, pos_vec, block_tables):
    """Absorbed MLA decode through a block table (see
    ``gqa_decode_paged`` for the layout/exactness and multi-position
    append contracts — identical here, with the [T*bs] gathered strip
    standing in for the dense [L] latent cache)."""
    q_abs, q_rope, latent_new, k_rope_new = mla_decode_qkv(p, cfg, x, pos_vec)
    cache = mla_append(cache, latent_new, k_rope_new,
                       paged_cells(block_tables, pos_vec,
                                   cache["latent"].shape[1]))
    ctx = mla_attend_paged(q_abs, q_rope, cache, block_tables, pos_vec, cfg)
    return mla_out(p["w_vb"], p["wo"], ctx, x.dtype), cache


# The pieces of one paged MLA decode step (jitted piecewise by
# ``repro.models.transformer``).
def mla_decode_qkv(p, cfg, x, pos_vec):
    """x [B,1,d], pos_vec [B] -> the query absorbed through ``w_kb``
    q_abs [B,H,r] (fp32), the roped query q_rope [B,H,rd], and each
    row's new latent [B,r] and rope key [B,rd]."""
    positions = jnp.reshape(pos_vec, (x.shape[0], 1)).astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    latent_new, k_rope_new = _mla_latent(p, cfg, x, positions)
    # bf16 operands + fp32 accumulation; the latent pool is never
    # up-cast (the f32 convert of the whole cache per layer was the
    # baseline's dominant traffic)
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_kb"],
                       preferred_element_type=jnp.float32)
    return q_abs, q_rope[:, 0], latent_new[:, 0], k_rope_new[:, 0]


def mla_append(cache, latent_new, k_rope_new, cells):
    """Scatter row b's latent [B,r] and rope key [B,rd] into ``cache``
    at ``cells``."""
    return {"latent": cache["latent"].at[cells].set(
                latent_new.astype(cache["latent"].dtype)),
            "k_rope": cache["k_rope"].at[cells].set(
                k_rope_new.astype(cache["k_rope"].dtype))}


def mla_attend_paged(q_abs, q_rope, cache, block_tables, pos_vec, cfg):
    """The attention core in the latent space: q_abs [B,H,r] and q_rope
    [B,H,rd] over the strip each row's table gathers from the pool,
    keys at index <= pos_vec[b] -> the latent context [B,H,r] fp32."""
    B, T = block_tables.shape
    latent, k_rope = cache["latent"], cache["k_rope"]
    bs = latent.shape[1]
    lg = latent[block_tables].reshape(B, T * bs, latent.shape[-1])
    rg = k_rope[block_tables].reshape(B, T * bs, k_rope.shape[-1])
    cdt = latent.dtype
    s = jnp.einsum("bhr,blr->bhl", q_abs.astype(cdt), lg,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,blk->bhl", q_rope.astype(cdt), rg,
                       preferred_element_type=jnp.float32)
    s = _mla_scale(s, cfg)
    valid = jnp.arange(T * bs)[None, :] <= pos_vec.astype(jnp.int32)[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blr->bhr", w.astype(cdt), lg,
                      preferred_element_type=jnp.float32)


def mla_out(w_vb, wo, ctx, dtype):
    """The latent context [B,H,r] expanded through ``w_vb`` and ``wo``
    -> [B,1,d]."""
    out = jnp.einsum("bhr,rhk->bhk", ctx.astype(w_vb.dtype), w_vb,
                     preferred_element_type=jnp.float32)
    return jnp.einsum("bshk,hkd->bsd", out[:, None].astype(dtype), wo)


# =====================================================================
# Cross-attention (enc-dec, VLM)
# =====================================================================
def cross_kv(p, enc):
    """Precompute K/V over frontend states enc [B,T,d]."""
    k = jnp.einsum("btd,dhk->bthk", enc, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    return {"k": k, "v": v}


def cross_attend(p, cfg, x, kv):
    """x [B,S,d] queries attend over precomputed kv (no mask)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    out = _sdpa(q, kv["k"], kv["v"], causal=False, window=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])
