"""One layer's GQA decode block as a few compiled programs.

Eagerly the block (norm, Q/K/V with rope, the K/V scatter, attention,
``wo``, the residual) is ~100 separate dispatches, and on a TPU each
one leaves the chip idle while the host issues it. These tests pin the
block to three jitted calls, with the paged kernel as a program of its
own; check that the calls the benchmark's warm-up makes (one per
block-table width, on the server's live layer-0 pool) are the ones the
served steps reuse; and check the compiled block against the same maths
run op by op.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import offload_engine as oe
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import transformer as tf
from repro.models.layers import rms_norm
from repro.serving import ContinuousOffloadServer

B, T, BS = 4, 3, 8
POS = [0, 5, 13, 23]             # within each row's T*BS strip


@pytest.fixture
def paged_impl():
    """Sets ``attn.PAGED_ATTN_IMPL`` for one test, and puts it back."""
    old = attn.PAGED_ATTN_IMPL

    def set_impl(impl):
        attn.PAGED_ATTN_IMPL = impl
    yield set_impl
    attn.PAGED_ATTN_IMPL = old


def _block_args(dtype, layout):
    """One layer's params and a decode call's inputs: ``layout`` "paged"
    gives a pool with a permuted block table, "dense" a [B, L] cache."""
    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x7b"), layers=1, d_model=64),
        dtype=jnp.dtype(dtype).name)
    p = oe._layer_slice(tf.init_params(cfg, jax.random.PRNGKey(0))["layers"],
                        0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(ks[0], (B, 1, cfg.d_model), dtype)
    pos = jnp.asarray(POS, jnp.int32)
    if layout == "paged":
        cache = attn.gqa_paged_cache_init(cfg, B * T + 1, BS, dtype)
        bt = jnp.asarray(np.random.default_rng(0).permutation(B * T)
                         .reshape(B, T), jnp.int32)
        extra = (bt,)
    else:
        cache = attn.gqa_cache_init(cfg, B, T * BS, dtype)
        extra = ()
    # earlier tokens already in the cache, so attention has keys to read
    cache = {k: jax.random.normal(kk, v.shape, dtype)
             for (k, v), kk in zip(cache.items(), ks[1:])}
    return p, cfg, (h, cache, pos) + extra


def _block(layout):
    return tf._attn_decode_paged if layout == "paged" else \
        tf._attn_decode_multipos


@pytest.mark.parametrize("layout,impl", [("paged", "xla"),
                                         ("paged", "pallas_interpret"),
                                         ("dense", "xla")])
def test_decode_block_is_at_most_four_jitted_calls(paged_impl, layout, impl):
    """Every top-level equation of the block is a jitted call, at most
    four of them; on the paged layout one is the kernel's own program
    (its device time is read under that name) unless ``impl`` is
    "xla"."""
    paged_impl(impl)
    p, cfg, args = _block_args(jnp.float32, layout)
    block = _block(layout)
    # a fresh closure each time: make_jaxpr caches traces by function,
    # and the block reads PAGED_ATTN_IMPL when it runs
    eqns = jax.make_jaxpr(lambda *a: block(p, cfg, *a))(*args).eqns
    assert 1 <= len(eqns) <= 4
    assert all(e.primitive.name in ("jit", "pjit") for e in eqns), \
        [e.primitive.name for e in eqns]
    names = [e.params["name"] for e in eqns]
    assert ("paged_attention" in names) == (layout == "paged"
                                            and impl != "xla"), names


@pytest.mark.parametrize("layout,impl", [("paged", "xla"),
                                         ("paged", "pallas_interpret"),
                                         ("dense", "xla")])
def test_compiled_decode_block_matches_op_by_op(paged_impl, layout, impl):
    """The compiled block == the composition it replaced (norm, the
    attention module's decode, the residual) run op by op, in bf16."""
    paged_impl(impl)
    p, cfg, args = _block_args(jnp.bfloat16, layout)
    h, cache = args[0], args[1]
    y, new = _block(layout)(p, cfg, *args)
    with jax.disable_jit():
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        decode = attn.gqa_decode_paged if layout == "paged" else \
            attn.gqa_decode_multipos
        y_ref, new_ref = decode(p["attn"], cfg, x, cache, *args[2:])
        y_ref = h + y_ref
    assert y.dtype == y_ref.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    for k in ("k", "v"):
        np.testing.assert_allclose(np.asarray(new[k], np.float32),
                                   np.asarray(new_ref[k], np.float32),
                                   rtol=2e-2, atol=2e-2)


def _attention_programs():
    return (tf._gqa_pre, tf._gqa_attend_paged, tf._gqa_post,
            kops.paged_attention)


def test_warmup_per_table_width_covers_served_steps(paged_impl):
    """Warm the paged block up the way the benchmark does: once per
    block-table width, on zeros of the step's shape and the server's
    live layer-0 pool. Serving then adds no entry to any attention
    program's cache, the warmed pool is not consumed (nothing is
    donated), and the tokens are those of a server never warmed."""
    paged_impl("pallas_interpret")
    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x7b"), layers=2, d_model=64, experts=4,
                vocab=128), dtype="float32")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    prompts, n_new, bs, cache_len = [[1, 2, 3, 4, 5], [9, 8, 7]], 6, 4, 16

    def server():
        return ContinuousOffloadServer(
            params, cfg, cache_slots=2, policy="lfu", max_batch=2,
            cache_len=cache_len, kv_block_size=bs,
            kv_num_blocks=2 * cache_len // bs)

    def serve(srv):
        rids = [srv.submit(p, max_new=n_new) for p in prompts]
        srv.run()
        return [srv.result(r) for r in rids]

    srv = server()
    eng, rows = srv.engine, srv._step_rows
    p0 = oe._layer_slice(eng.params["layers"], 0)
    h0 = jnp.zeros((rows, 1, cfg.d_model), eng.dtype)
    pos0 = jnp.zeros((rows,), jnp.int32)
    pool0 = srv.state["layers"][0]
    for width in range(1, cache_len // bs + 1):
        bt = jnp.asarray(np.full((rows, width), srv.paged.sink, np.int32))
        jax.block_until_ready(tf._attn_decode_paged(
            p0, cfg, h0, pool0, pos0, bt))
    sizes = [f._cache_size() for f in _attention_programs()]
    got = serve(srv)
    assert [f._cache_size() for f in _attention_programs()] == sizes
    assert not any(a.is_deleted() for a in pool0.values())
    assert np.isfinite(np.asarray(pool0["k"])).all()
    assert got == serve(server())
