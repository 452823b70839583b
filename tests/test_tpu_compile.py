"""Compiles of the serving path's Pallas kernels for a described TPU v5e
at Mixtral-8x7B width (d_model 4096, expert d_ff 14336, 32 q / 8 kv
heads of 128). Nothing runs: the TPU compiler, installed with JAX,
compiles for a chip that is described and not attached, and refuses
what the chip would refuse (VMEM overflow, unaligned tiles) — which
interpret-mode tests cannot show.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, F = 4096, 14336
H, KV, HD = 32, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("U", [1, 2, 4])
def test_moe_ffn_pallas_compiles_at_mixtral_width(one_chip, U, dtype):
    """The grouped FFN over U resident experts and an 8-row decode
    batch: the auto-chosen blocks must fit scoped VMEM."""
    args = (_spec((U, 8, D), dtype, one_chip),
            _spec((U, D, F), dtype, one_chip),
            _spec((U, D, F), dtype, one_chip),
            _spec((U, F, D), dtype, one_chip))
    fn = jax.jit(functools.partial(ops.moe_ffn, impl="pallas"))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (U, 8, D) and out.dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_pallas_compiles_at_mixtral_heads(one_chip, dtype):
    """Decode attention for 4 rows over a pool of 16-token blocks."""
    B, N, BS, T = 4, 9, 16, 2
    args = (_spec((B, H, HD), dtype, one_chip),
            _spec((N, BS, KV, HD), dtype, one_chip),
            _spec((N, BS, KV, HD), dtype, one_chip),
            _spec((B, T), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))
    fn = jax.jit(functools.partial(ops.paged_attention, impl="pallas"))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (B, H, HD)


@pytest.mark.parametrize("rows,width", [(64, 1), (64, 65)])
def test_paged_decode_block_programs_compile_at_mixtral_width(one_chip, rows,
                                                              width):
    """The paged decode block's programs around the kernel (norm, Q/K/V
    with rope and the K/V scatter; ``wo`` and the residual), bf16, at
    the narrowest and widest block table a served step gives."""
    from repro.configs import get_config
    from repro.models import transformer as tf
    cfg = get_config("mixtral-8x7b")
    bf = jnp.bfloat16
    N, BS = rows * width, 16
    pa = {"wq": _spec((D, H, HD), bf, one_chip),
          "wk": _spec((D, KV, HD), bf, one_chip),
          "wv": _spec((D, KV, HD), bf, one_chip),
          "wo": _spec((H, HD, D), bf, one_chip)}
    pool = {k: _spec((N, BS, KV, HD), bf, one_chip) for k in ("k", "v")}
    h = _spec((rows, 1, D), bf, one_chip)
    pre = tf._gqa_pre.lower(
        _spec((D,), bf, one_chip), pa, cfg, h, pool,
        _spec((rows,), jnp.int32, one_chip),
        _spec((rows, width), jnp.int32, one_chip)).compile()
    q, new_pool = pre.out_info
    assert q.shape == (rows, H, HD) and new_pool["k"].shape == (N, BS, KV, HD)
    post = tf._gqa_post.lower(pa["wo"], h,
                              _spec((rows, H, HD), bf, one_chip)).compile()
    assert post.out_info.shape == (rows, 1, D)
