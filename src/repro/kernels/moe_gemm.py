"""Pallas TPU kernel: fused grouped-expert SwiGLU FFN.

Computes, for every expert e over its capacity-dispatched token block
x[e] ([C, d]):    y[e] = (silu(x[e] @ w1[e]) * (x[e] @ w3[e])) @ w2[e]

This is the hot GEMM of the paper's workload (the expert FFN that
offloading streams weights for). TPU-native tiling:

  grid = (E, C/bc, F/bf), f innermost so the second GEMM accumulates
  into the fp32 output block across f-steps (classic K-loop pattern).

VMEM working set per step: x (bc×d) + w1,w3 (d×bf each) + w2 (bf×d)
in the input dtype + the fp32 output block (bc×d), all double-buffered
by the pipeline. It must fit the compiler's scoped VMEM limit (16 MiB
on v5e), not the chip's whole VMEM: at d=4096 and bc=8 that allows
bf=256 in bf16 (~12.4 MiB) and bf=128 in fp32 (~12.5 MiB), and
refuses bf=512 in either. ``ops.moe_ffn_blocks`` picks the blocks; all
matmul dims are kept multiples of 128 for the MXU by padding in
``ops.moe_ffn``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                      # [bc, d]
    h = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    g = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * g).astype(x.dtype)   # [bc, bf]
    o_ref[0] += jnp.dot(a, w2_ref[0], preferred_element_type=jnp.float32)


def moe_gemm_pallas(x_e, w1, w3, w2, *, block_c: int = 128,
                    block_f: int = 512, interpret: bool = False):
    """x_e [E, C, d]; w1/w3 [E, d, F]; w2 [E, F, d] -> [E, C, d] fp32.

    C must divide by block_c, F by block_f (ops.py pads).
    """
    E, C, d = x_e.shape
    F = w1.shape[-1]
    assert C % block_c == 0 and F % block_f == 0, (C, F, block_c, block_f)
    grid = (E, C // block_c, F // block_f)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, d, block_f), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, d, block_f), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, block_f, d), lambda e, c, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), lambda e, c, f: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), jnp.float32),
        interpret=interpret,
    )(x_e, w1, w3, w2)
