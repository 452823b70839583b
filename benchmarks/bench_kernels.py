"""Kernel micro-benchmarks: XLA-path wall time on this CPU (the Pallas
path is TPU-target; interpret mode checks correctness, not speed) +
analytic MXU/VMEM occupancy of the chosen BlockSpecs."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit


def timeit(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> None:
    from repro.kernels import ops
    rng = np.random.default_rng(0)

    E, C, d, F = 8, 256, 512, 1024
    x = jnp.asarray(rng.normal(size=(E, C, d)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, d, F)) * 0.05, jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(E, d, F)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(E, F, d)) * 0.05, jnp.float32)
    us = timeit(lambda: ops.moe_ffn(x, w1, w3, w2, impl="xla"))
    flops = 2 * 3 * E * C * d * F
    emit("kernel/moe_gemm_xla_cpu", us, f"gflops={flops / us / 1e3:.1f}")

    # grouped-GEMM impl comparison on a decode-shaped problem: ref
    # (einsum oracle) vs xla (batched dot) vs pallas interpret mode,
    # wall time + worst-case deviation from the oracle (PR 9 hot path)
    Eg, Cg, dg, Fg = 4, 128, 256, 512
    xg = jnp.asarray(rng.normal(size=(Eg, Cg, dg)) * 0.5, jnp.float32)
    g1 = jnp.asarray(rng.normal(size=(Eg, dg, Fg)) * 0.05, jnp.float32)
    g3 = jnp.asarray(rng.normal(size=(Eg, dg, Fg)) * 0.05, jnp.float32)
    g2 = jnp.asarray(rng.normal(size=(Eg, Fg, dg)) * 0.05, jnp.float32)
    want = np.asarray(ops.moe_ffn(xg, g1, g3, g2, impl="ref"))
    for impl in ("ref", "xla", "pallas_interpret"):
        us = timeit(lambda: ops.moe_ffn(xg, g1, g3, g2, impl=impl),
                    iters=1 if impl == "pallas_interpret" else 5)
        diff = float(np.max(np.abs(
            np.asarray(ops.moe_ffn(xg, g1, g3, g2, impl=impl)) - want)))
        emit(f"kernel/moe_gemm_grouped_{impl}", us,
             f"E{Eg}xC{Cg}xd{dg}xF{Fg} max_abs_diff={diff:.2e}")

    # VMEM working set of the blocks moe_ffn chooses for an 8-row
    # decode batch at Mixtral width (d=4096, F=14336)
    for dt in (jnp.bfloat16, jnp.float32):
        bc, bf = ops.moe_ffn_blocks(8, 4096, 14336, dt)
        vmem = ops.moe_ffn_vmem_bytes(bc, bf, 4096, dt)
        emit(f"kernel/moe_gemm_vmem_bytes_{jnp.dtype(dt).name}", 0.0,
             f"block_c={bc} block_f={bf} {vmem / 2**20:.2f}MiB")

    B, S, H, hd = 2, 1024, 8, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, hd)), jnp.float32)
    us = timeit(lambda: ops.flash_attention(q, k, v, impl="xla"))
    emit("kernel/flash_attn_xla_cpu", us, f"S={S}")

    vmem_fa = (128 * hd * 2 * 3 + 128 * 128 * 4 + 128 * hd * 4 + 2 * 128 * 4)
    emit("kernel/flash_attn_vmem_bytes", 0.0, f"{vmem_fa / 2**10:.0f}KiB")

    # ssd_chunk: XLA oracle wall time + VMEM claim of the Pallas tiling
    G, Q, Hh, P, N = 8, 128, 16, 64, 128
    dA = -jnp.abs(jnp.asarray(rng.normal(size=(G, Q, Hh)), jnp.float32)) * 0.1
    xw = jnp.asarray(rng.normal(size=(G, Q, Hh, P)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    us = timeit(lambda: ops.ssd_chunk(dA, xw, Bm, Cm, impl="xla")[0])
    emit("kernel/ssd_chunk_xla_cpu", us, f"G{G}xQ{Q}xH{Hh}")
    bh = 8
    vmem_ssd = (Q * bh * P * 4 + 2 * Q * N * 4 + 2 * Q * Q * 4
                + Q * bh * P * 4 + bh * P * N * 4)
    emit("kernel/ssd_chunk_vmem_bytes", 0.0,
         f"{vmem_ssd / 2**20:.2f}MiB_per_grid_step")


if __name__ == "__main__":
    run()
