"""Jit'd public wrappers around the Pallas kernels: shape checks,
MXU-friendly padding, GQA broadcast, and an ``impl`` switch:

  impl="pallas"            — real TPU lowering (target hardware)
  impl="pallas_interpret"  — kernel body interpreted on CPU (tests)
  impl="xla"               — batched-dot XLA lowering (default on CPU)
  impl="ref"               — the unfused jnp oracle (moe_ffn only)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gemm import moe_gemm_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _moe_ffn_xla(x_e, w1, w3, w2):
    """Batched-dot XLA lowering of the grouped SwiGLU FFN (one fused
    dot_general chain per expert via vmap) — the production CPU/GPU
    fallback, distinct from the unfused einsum oracle in ``ref``."""
    def one(x, a, b, c):
        x = x.astype(jnp.float32)
        h = x @ a.astype(jnp.float32)
        g = x @ b.astype(jnp.float32)
        return (jax.nn.silu(h) * g) @ c.astype(jnp.float32)
    return jax.vmap(one)(x_e, w1, w3, w2)


def _aligned_block(n: int, cap: int, mult: int) -> int:
    """Largest multiple of ``mult`` that is <= ``cap`` and divides
    ``n`` rounded up to ``mult`` (the padded extent). ``mult`` itself
    always qualifies, so the search terminates."""
    n_p = n + (-n) % mult
    for b in range(min(cap, n_p) - min(cap, n_p) % mult, 0, -mult):
        if n_p % b == 0:
            return b
    return mult


# Default scoped-VMEM limit of a TPU kernel (16 MiB on v4/v5e; the
# chips hold more VMEM, but the compiler refuses a kernel whose blocks
# exceed the scoped limit), less headroom for the kernel's own
# intermediates.
_VMEM_BLOCK_BUDGET = 14 * 2**20


def moe_ffn_vmem_bytes(block_c: int, block_f: int, d: int, dtype) -> int:
    """VMEM the grouped-FFN kernel's blocks take: x [bc, d] and the
    w1/w3 [d, bf] and w2 [bf, d] tiles in ``dtype``, plus the fp32
    output block [bc, d], each double-buffered by the pipeline."""
    s = jnp.dtype(dtype).itemsize
    return 2 * (block_c * d + 3 * d * block_f) * s + 2 * block_c * d * 4


def moe_ffn_blocks(C: int, d: int, F: int, dtype):
    """Auto-chosen ``(block_c, block_f)`` for ``moe_ffn``: TPU-tile
    aligned (sublane multiple of 8, lane multiple of 128), and the
    largest ``block_f <= 512`` whose working set fits the scoped VMEM
    budget at the padded width ``d`` (at Mixtral's d=4096 that is 256 in
    bf16 and 128 in fp32)."""
    bc = _aligned_block(C, 128, 8)
    d_p = d + (-d) % 128
    for cap in (512, 384, 256):
        bf = _aligned_block(F, cap, 128)
        if moe_ffn_vmem_bytes(bc, bf, d_p, dtype) <= _VMEM_BLOCK_BUDGET:
            return bc, bf
    return bc, 128


@functools.partial(jax.jit, static_argnames=("impl", "block_c", "block_f"))
def moe_ffn(x_e, w1, w3, w2, *, impl: str = "xla",
            block_c: int = None, block_f: int = None):
    """Grouped expert SwiGLU FFN. x_e [E,C,d] -> [E,C,d] fp32.

    The pallas path pads every GEMM extent and slices the result back,
    so ragged shapes (``C % block_c != 0``, ``F % block_f != 0``, odd
    ``d``) are exact — parity-tested vs xla/ref. With the default
    ``block_c=block_f=None`` the blocks come from ``moe_ffn_blocks``
    (tile aligned, ``d`` padded to 128, working set within scoped
    VMEM); explicitly passed blocks are honored as-is (interpret-mode
    testing knob — real-TPU alignment and VMEM are then the caller's
    responsibility).
    """
    if impl == "ref":
        return ref.moe_gemm_ref(x_e, w1, w3, w2)
    if impl == "xla":
        return _moe_ffn_xla(x_e, w1, w3, w2)
    interpret = impl == "pallas_interpret"
    E, C, d = x_e.shape
    F = w1.shape[-1]
    auto_c, auto_f = moe_ffn_blocks(C, d, F, x_e.dtype)
    bc = block_c if block_c is not None else auto_c
    bf = block_f if block_f is not None else auto_f
    x_p, C0 = _pad_to(x_e, 1, bc)
    x_p, _ = _pad_to(x_p, 2, 128)           # MXU contraction dim
    w1_p, _ = _pad_to(_pad_to(w1, 1, 128)[0], 2, bf)
    w3_p, _ = _pad_to(_pad_to(w3, 1, 128)[0], 2, bf)
    w2_p, _ = _pad_to(_pad_to(w2, 2, 128)[0], 1, bf)
    out = moe_gemm_pallas(x_p, w1_p, w3_p, w2_p, block_c=bc, block_f=bf,
                          interpret=interpret)
    return out[:, :C0, :d]


@functools.partial(jax.jit, static_argnames=("impl", "block_h"))
def ssd_chunk(dA, xw, Bm, Cm, *, impl: str = "xla", block_h: int = 8):
    """SSD intra-chunk: dA [G,Q,H], xw [G,Q,H,P], Bm/Cm [G,Q,N] ->
    (Y_intra [G,Q,H,P], S_chunk [G,H,P,N]), both fp32."""
    if impl == "xla":
        return ref.ssd_chunk_ref(dA, xw, Bm, Cm)
    H = dA.shape[-1]
    bh = block_h
    while H % bh:
        bh -= 1
    return ssd_chunk_pallas(dA, xw, Bm, Cm, block_h=bh,
                            interpret=impl == "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("impl", "causal", "window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "xla", block_q: int = 128,
                    block_k: int = 128):
    """Multi-head attention over [B, S, H, hd] q/k and [B, S, KV, vd] v
    (GQA broadcast inside; v may be narrower than q/k — MLA).
    Returns [B, Sq, H, vd]."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, vd)

    if impl == "xla":
        out = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        interpret = impl == "pallas_interpret"
        bq = min(block_q, Sq)
        bk = min(block_k, Sk)
        qp, Sq0 = _pad_to(qf, 1, bq)
        kp, _ = _pad_to(kf, 1, bk)
        vp, _ = _pad_to(vf, 1, bk)
        out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                     block_q=bq, block_k=bk, seq_k=Sk,
                                     interpret=interpret)
        out = out[:, :Sq0]
    return out.reshape(B, H, Sq, vd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention(q, k_pool, v_pool, block_tables, pos, *,
                    impl: str = "xla"):
    """Single-token decode attention over a paged KV pool.

    q [B, H, hd]; k/v_pool [num_blocks, block_size, KV, hd] (the
    serving layer's shared block pool); block_tables [B, T] int32 maps
    each row's logical blocks to physical ones; pos [B] int32 bounds
    each row's visible keys (logical index <= pos). GQA grouping is
    H // KV. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV = k_pool.shape[2]
    assert H % KV == 0, f"q heads {H} not grouped over {KV} kv heads"
    block_tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    if impl == "xla":
        return ref.paged_attention_ref(q, k_pool, v_pool, block_tables, pos)
    out = paged_attention_pallas(
        q.reshape(B, KV, H // KV, hd), k_pool, v_pool, block_tables, pos,
        interpret=impl == "pallas_interpret")
    return out.reshape(B, H, hd)
