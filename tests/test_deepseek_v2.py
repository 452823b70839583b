"""DeepSeek-V2 on the offload path at tiny widths (float32, CPU): the
group-limited router against a transcription of the published gate,
YaRN's frequencies and softmax scale, the served logits against the
model's full forward, a chip's share of the experts, the dense first
layer, the compiled MLA decode block and the spans it adds."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import offload_engine as oe
from repro.core.expert_store import ExpertStore
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.models.layers import rms_norm, swiglu
from repro.serving import ContinuousOffloadServer

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
# every published mechanism at tiny widths: 16 experts in 8 groups, so
# 4 ranks of 2 groups hold 4 experts each
CFG = ModelConfig(
    name="dsv2-tiny", family="moe", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=128, use_mla=True,
    kv_lora_rank=32, qk_rope_dim=16, q_lora_rank=48, rope_scaling=YARN,
    num_experts=16, num_experts_per_tok=6, num_shared_experts=2,
    moe_d_ff=32, first_k_dense_replace=1,
    topk_method="group_limited_greedy", n_group=8, topk_group=3,
    norm_topk_prob=False, routed_scaling_factor=16.0, norm_eps=1e-6,
    dtype="float32")


@pytest.fixture(scope="module")
def params():
    return tf.init_params(CFG, jax.random.PRNGKey(0))


def _published_gate(logits, n_group, topk_group, k, scale):
    """DeepSeek-V2's MoEGate, group_limited_greedy, transcribed to numpy
    (scores in float64): ids [n, k] and weights [n, k]."""
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True)
    n, E = s.shape
    group_scores = s.reshape(n, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, -1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, -1)
    score_mask = np.repeat(group_mask, E // n_group, -1)
    tmp = np.where(score_mask > 0, s, 0.0)
    idx = np.argsort(-tmp, -1)[:, :k]
    return idx, np.take_along_axis(s, idx, -1) * scale


@pytest.mark.parametrize("xp", [np, jnp])
def test_group_limited_router_is_the_published_gate(xp):
    cfg = get_config("deepseek-v2-236b")
    logits = np.random.default_rng(0).normal(size=(64, 160)).astype(
        np.float32) * 3
    w, ids = moe_lib.route(xp.asarray(logits), cfg, xp=xp)
    want_ids, want_w = _published_gate(logits.astype(np.float64), 8, 3, 6,
                                       16.0)
    w, ids = np.asarray(w), np.asarray(ids)
    for b in range(len(logits)):
        o, p = np.argsort(ids[b]), np.argsort(want_ids[b])
        assert ids[b][o].tolist() == want_ids[b][p].tolist()
        np.testing.assert_allclose(w[b][o], want_w[b][p], rtol=1e-5)
    # not renormalised: the weights sum to 16 x the picks' softmax mass
    assert (w.sum(-1) < 16.0).all()
    # every pick lies in the 3 groups of highest best score
    assert (np.unique(ids // 20, axis=None).size <= 8
            and max(len(set(r)) for r in (ids // 20).tolist()) <= 3)


@pytest.mark.parametrize("xp", [np, jnp])
def test_greedy_router_is_unchanged(xp):
    """Mixtral's routing: the softmax over the top-k logits, computed
    as before (bit for bit on the host)."""
    cfg = get_config("mixtral-8x7b")
    logits = np.random.default_rng(1).normal(size=(32, 8)).astype(np.float32)
    w, ids = moe_lib.route(xp.asarray(logits), cfg, xp=xp)
    old_ids = np.argsort(-logits, axis=-1)[:, :2]
    top = np.take_along_axis(logits, old_ids, axis=-1)
    top = np.exp(top - top.max(axis=-1, keepdims=True))
    old = top / top.sum(axis=-1, keepdims=True)
    if xp is np:
        assert (ids == old_ids).all() and (w == old).all()
    else:
        np.testing.assert_array_equal(np.asarray(ids), old_ids)
        np.testing.assert_allclose(np.asarray(w), old, rtol=1e-6)


def test_yarn_inv_freq_and_softmax_scale():
    """At 64 rope dims: correction dims floor(10.47) = 10 and
    ceil(22.51) = 23, so dims 0-9 keep θ^(-2i/64), 23-31 are divided by
    40, the ramp blends between; the scale is 192^-0.5 · mscale² with
    mscale = 0.1 · 0.707 · ln 40 + 1."""
    cfg = get_config("deepseek-v2-236b")
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(attn.mla_inv_freq(cfg), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert attn.mla_softmax_mscale(cfg) == pytest.approx(mscale ** 2)
    # plain rope where the config has no scaling
    plain = dataclasses.replace(cfg, rope_scaling=())
    np.testing.assert_allclose(attn.mla_inv_freq(plain), extra, rtol=1e-6)
    assert attn.mla_softmax_mscale(plain) == 1.0


def _served_logits(cfg, params, prompts, n_new, store=None, **kw):
    """Serve ``prompts`` (paged latent KV, prompt chunks of 4); the
    server and each request's output-token logits rows."""
    srv = ContinuousOffloadServer(
        params, cfg, store=store, cache_slots=4, policy="lfu", max_batch=2,
        cache_len=32, kv_block_size=8, kv_num_blocks=8, prefill_chunk=4,
        **kw)
    rids = [srv.submit(p, max_new=n_new) for p in prompts]
    rows = {r: [] for r in rids}

    def req(rid):
        live = [r for r in srv.slots if r is not None] + list(srv.queue)
        return {r.rid: r for r in live}.get(rid) or srv.finished[rid]
    while srv.pending or srv.num_active:
        n = len(srv.trace.steps)
        outs = {r: len(req(r).out) for r in rids}
        srv.step()
        recs = srv.trace.steps[n:]
        last = {rid: i for i, rid in enumerate(recs[0].request_ids)}
        for r in rids:
            if len(req(r).out) > outs[r]:
                rows[r].append(np.asarray(srv.last_logits[last[r]]))
    return srv, rids, rows


def test_served_logits_match_the_full_forward(params):
    """Prefill, then decode through the paged latent cache of
    ContinuousOffloadServer, every expert held: each output token's
    logits equal the model's full forward over the same tokens."""
    prompts = [[5, 9, 2, 7, 7, 1], [3, 8, 11]]
    srv, rids, rows = _served_logits(CFG, params, prompts, 5)
    for r, p in zip(rids, prompts):
        seq = srv.result(r)[:-1]
        h, _ = tf.forward(params, CFG, jnp.asarray([seq], jnp.int32),
                          moe_path="dense")
        want = np.asarray(tf.logits_from_hidden(params, CFG, h)[0])
        got = np.stack(rows[r])
        np.testing.assert_allclose(got, want[len(p) - 1:len(p) - 1 + len(got)],
                                   rtol=2e-4, atol=2e-4)


def _share_store(full: ExpertStore, held):
    store = ExpertStore()
    for l, e in full.keys():
        if e in held:
            store.put((l, e), full.fetch((l, e)))
    return store


def test_group_shares_add_up_to_the_uncut_layer(params):
    """Four ranks of 2 groups each: their MoE layer outputs, with the
    shared experts (which every rank computes) counted once, sum to the
    uncut layer (model-configs guide, section 4)."""
    full = ExpertStore.from_params(params, CFG)
    B = 3
    h = jax.random.normal(jax.random.PRNGKey(5), (B, 1, CFG.d_model))
    p_full = oe._layer_slice(params["layers"], 0)          # model layer 1
    x = rms_norm(h, p_full["ln2"], CFG.norm_eps)
    outs, held_rows = [], []
    for rank in range(4):
        held = set(range(4 * rank, 4 * rank + 4))
        eng = oe.OffloadEngine(params, CFG, cache_slots=4, policy="lfu",
                               store=_share_store(full, held))
        assert eng.held == tuple(sorted(held))
        p_l = eng._block(1)
        y, *_ = eng._moe_offloaded(p_l, 1, h, (), (), {}, [1] * B,
                                   [0] * B, [True] * B)
        outs.append(np.asarray(y - h))
        held_rows.append(eng.trace.steps[-1].request_activated)
    shared = np.asarray(swiglu(p_full["moe"]["shared"], x))
    uncut, _ = moe_lib.moe_dense(p_full["moe"], CFG, x)
    np.testing.assert_allclose(sum(outs) - 3 * shared, np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    # each row's 6 picks fall to the ranks that hold them
    for b in range(B):
        picks = sorted(e for rows in held_rows for e in rows[b])
        assert len(picks) == 6


def test_spec_guess_is_the_group_limited_route(params):
    """The speculative guess is the union of the model router's picks,
    group limits included, not a plain top-k over every expert."""
    from repro.core.prefetch import SpeculativePrefetcher
    p = oe._layer_slice(params["layers"], 0)
    h = jax.random.normal(jax.random.PRNGKey(3), (8, 1, CFG.d_model))
    logits = np.asarray((rms_norm(h, p["ln2"], CFG.norm_eps)
                         @ p["moe"]["router"])[:, 0, :])
    _, ids = moe_lib.route(logits, CFG, xp=np)
    guess = SpeculativePrefetcher(CFG).guess(h, p["ln2"], p["moe"]["router"])
    assert guess == tuple(int(e) for e in np.unique(ids))
    assert guess != tuple(int(e) for e in np.unique(
        np.argsort(-logits, axis=-1)[:, :6]))


@pytest.mark.parametrize("prefetch", ["spec", "markov", "learned"])
def test_prefetch_on_a_partial_store_guesses_held_experts_only(params,
                                                               prefetch):
    """A rank holding groups 2-3 of 8 (ids 4-7 of 16): every predictor
    guesses over all 16 experts, and only held ids are prefetched,
    recorded as guesses and cached; prefetching leaves the logits as
    they are without it."""
    held = set(range(4, 8))
    share = _share_store(ExpertStore.from_params(params, CFG), held)
    prompts = [[5, 9, 2, 7, 7, 1], [3, 8, 11]]
    _, _, want = _served_logits(CFG, params, prompts, 5, store=share)
    srv, rids, rows = _served_logits(CFG, params, prompts, 5, store=share,
                                     prefetch=prefetch)
    eng = srv.engine
    assert eng.stats()["prefetches"] > 0
    assert all(set(s.spec_guess) <= held for s in srv.trace.steps)
    assert all(set(c.cached_ids()) <= held for c in eng.moe_caches)
    for r in rids:
        np.testing.assert_array_equal(np.stack(rows[r]), np.stack(want[r]))


def test_dense_first_layer_has_no_cache_and_no_record(params):
    eng = oe.OffloadEngine(params, CFG, cache_slots=4, policy="lfu")
    assert eng.caches[0] is None and eng.n_dense == 1
    assert all(c is not None for c in eng.caches[1:])
    assert {l for l, _ in eng.store.keys()} == {1, 2}
    eng.generate([1, 2, 3], 4)
    assert {s.layer for s in eng.trace.steps} == {1, 2}
    assert eng.stats()["hits"] + eng.stats()["misses"] > 0


def test_every_moe_layer_holds_the_same_experts(params):
    full = ExpertStore.from_params(params, CFG)
    store = ExpertStore()
    for l, e in full.keys():
        if (l, e) != (2, 3):
            store.put((l, e), full.fetch((l, e)))
    with pytest.raises(ValueError, match="same non-empty set"):
        oe.OffloadEngine(params, CFG, cache_slots=4, store=store)


def _mla_block_args(dtype):
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype).name)
    p = oe._layer_slice(tf.init_params(cfg, jax.random.PRNGKey(0))["layers"],
                        0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, T, BS = 4, 3, 8
    h = jax.random.normal(ks[0], (B, 1, cfg.d_model), dtype)
    pos = jnp.asarray([0, 5, 13, 23], jnp.int32)
    cache = attn.mla_paged_cache_init(cfg, B * T + 1, BS, dtype)
    cache = {k: jax.random.normal(kk, v.shape, dtype)
             for (k, v), kk in zip(cache.items(), ks[1:])}
    bt = jnp.asarray(np.random.default_rng(0).permutation(B * T)
                     .reshape(B, T), jnp.int32)
    return p, cfg, (h, cache, pos, bt)


def test_mla_decode_block_is_at_most_four_jitted_calls():
    """As tests/test_attention_dispatch.py counts the GQA block: every
    top-level equation of the paged MLA block is a jitted call, at most
    four, one of them the attention core ``mla_attend_paged``."""
    p, cfg, args = _mla_block_args(jnp.float32)
    eqns = jax.make_jaxpr(
        lambda *a: tf._attn_decode_paged(p, cfg, *a))(*args).eqns
    assert 1 <= len(eqns) <= 4
    assert all(e.primitive.name in ("jit", "pjit") for e in eqns)
    assert "mla_attend_paged" in [e.params["name"] for e in eqns]


def test_compiled_mla_block_matches_op_by_op():
    p, cfg, args = _mla_block_args(jnp.bfloat16)
    h, cache = args[0], args[1]
    y, new = tf._attn_decode_paged(p, cfg, *args)
    with jax.disable_jit():
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        y_ref, new_ref = attn.mla_decode_paged(p["attn"], cfg, x, cache,
                                               *args[2:])
        y_ref = h + y_ref
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    for k in ("latent", "k_rope"):
        np.testing.assert_allclose(np.asarray(new[k], np.float32),
                                   np.asarray(new_ref[k], np.float32),
                                   rtol=2e-2, atol=2e-2)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    span's name and arguments, ``set_metadata`` included."""
    spans = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        _Recorder.spans.append(self)
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.args.update(args)


def test_spans_of_the_dense_layer_shared_experts_and_held_share(
        params, monkeypatch):
    """``engine.dense_ffn`` wraps layer 0's FFN, ``engine.shared`` each
    MoE layer's shared experts, and ``engine.route`` counts the held and
    the routed (row, expert) picks: here rank 0 of 4 holds experts
    0-3."""
    full = ExpertStore.from_params(params, CFG)
    _Recorder.spans = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    srv, rids, _ = _served_logits(CFG, params, [[5, 9, 2, 7, 7, 1]], 4,
                                  store=_share_store(full, set(range(4))))
    by = {}
    for s in _Recorder.spans:
        by.setdefault(s.name, []).append(s.args)
    assert {a["layer"] for a in by["engine.dense_ffn"]} == {0}
    assert all(set(a) == {"layer"} for a in by["engine.dense_ffn"])
    assert {a["layer"] for a in by["engine.shared"]} == {1, 2}
    assert all(set(a) == {"layer", "rows"} for a in by["engine.shared"])
    routes = by["engine.route"]
    assert all(set(a) == {"layer", "held", "routed"} for a in routes)
    records = srv.trace.steps
    assert len(routes) == len(records)
    for a, rec in zip(routes, records):
        assert a["routed"] == 6 * len(rec.request_ids)
        assert a["held"] == sum(len(x) for x in rec.request_activated)
        assert all(set(x) <= {0, 1, 2, 3} for x in rec.request_activated)
    assert 0 < sum(a["held"] for a in routes) < sum(a["routed"]
                                                     for a in routes)
