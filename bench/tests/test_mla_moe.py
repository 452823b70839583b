"""DeepSeek-V2's module (``models/mla_moe.py``) at tiny widths on the
CPU: the group-limited Zipf calibration, the held share, the reference
against the serving program (prefill, then decode through the paged
latent cache), and ``run.py`` end to end."""
import copy
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

import spec as bspec
import tiny

MLA = bspec.load_module(bspec.BENCH / "models" / "mla_moe.py", "mla_moe_t")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
TINY_MLA = {
    "reference": "mla_moe",
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": 48, "moe_intermediate_size": 32, "intermediate_size": 128,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "n_group": 8,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "norm_topk_prob": False, "routed_scaling_factor": 16,
    "vocab_size": 512, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "rope_scaling": YARN,
    "n_routed_experts": 4,
    "expert_parallel": {"ranks": 4, "rank": 0, "n_routed_experts": 16},
    "program": {"name": "tiny-mla", "family": "moe", "num_layers": 3,
                "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                "pos_emb": "rope", "rope_theta": 10000.0, "use_mla": True,
                "kv_lora_rank": 32, "qk_rope_dim": 16, "q_lora_rank": 48,
                "rope_scaling": YARN, "num_experts": 16,
                "num_experts_per_tok": 6, "num_shared_experts": 2,
                "moe_d_ff": 32, "first_k_dense_replace": 1,
                "topk_method": "group_limited_greedy", "n_group": 8,
                "topk_group": 3, "norm_topk_prob": False,
                "routed_scaling_factor": 16.0, "norm_eps": 1e-06,
                "dtype": "float32"},
    "serving": {"cache_slots": 2, "policy": "lfu", "ffn_impl": "xla",
                "paged_attn_impl": "xla", "kv_block_size": 16},
    "correct": {"max_logit_err": 0.01, "max_logit_gap": 0.05,
                "route_tie": 0.005, "check_tokens": 40},
}
SEED = 2**31 + 77


def test_published_config_matches_the_layout():
    c = bspec.config("deepseek-v2-236b")
    layers, held, experts = bspec.expert_layout(MLA, c)
    assert layers == [1, 2, 3, 4] and held == list(range(40))
    assert experts == 160 and c["n_routed_experts"] == 40
    m = MLA.dims(c)
    assert (m.d, m.H, m.rq, m.r, m.rd, m.F, m.Fd, m.Fs, m.k) == (
        5120, 128, 1536, 512, 64, 1536, 12288, 3072, 6)


@pytest.mark.parametrize("seed", [2**31 + 3, 2**31 + 4])
def test_calibration_gives_each_group_an_eighth(seed):
    """Fresh draws through the published selection, the pattern
    shuffled inside each group by the seed: every group takes 1/8 of the
    picks, and the held groups 0-1 a quarter, within 0.01."""
    c = bspec.config("deepseek-v2-236b")
    m = MLA.dims(c)
    off = MLA.zipf_offsets(c, seed, 1.0)[1]
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((20000, m.E))
    z *= np.sqrt(m.E) / np.linalg.norm(z, axis=1, keepdims=True)
    top = MLA.group_select(off[None] + z, m.G, m.Gk, m.k)
    share = np.bincount(top.ravel(), minlength=m.E) / top.size
    groups = share.reshape(m.G, -1).sum(1)
    np.testing.assert_allclose(groups, 1 / 8, atol=0.01)
    assert abs(groups[:2].sum() - 0.25) < 0.01
    # Zipf 1.0 inside a group: the most popular expert of each group
    # takes ~ 1 / H_20 of its group's picks
    top_share = share.reshape(m.G, -1).max(1) / groups
    np.testing.assert_allclose(top_share, 1 / np.sum(1 / np.arange(1, 21)),
                               atol=0.03)


def test_yarn_matches_hand_computed_values():
    """At DeepSeek-V2's 64 rope dims: correction dims 10.47 -> 10 and
    22.51 -> 23, so dims 0-9 keep θ^(-2i/64), dims 23-31 are divided by
    40, and the softmax scale is 192^-0.5 (0.1 · 0.707 · ln 40 + 1)²."""
    m = MLA.dims(bspec.config("deepseek-v2-236b"))
    inv, cs, scale = MLA.yarn(m)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert cs == 1.0
    assert scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


def _serve(c, seed, prompts, n_new):
    """Serve ``prompts`` through ContinuousOffloadServer (paged latent
    KV, prompt chunks of 4) on the module's weights and store; the
    tokens, each output token's logits row and the served routes."""
    import driver
    from repro.configs.base import ModelConfig
    from repro.serving import ContinuousOffloadServer
    w = MLA.Weights(c, seed, 1.0)
    layers, held, _ = MLA.layout(c)
    srv = ContinuousOffloadServer(
        w.params(), ModelConfig(**c["program"]),
        store=driver.fill_store(w, layers, held), cache_slots=2,
        policy="lfu", max_batch=2, cache_len=32, kv_block_size=16,
        kv_num_blocks=4, prefill_chunk=4)
    rids = [srv.submit(p, max_new=n_new) for p in prompts]
    logits = {r: [] for r in rids}
    while srv.pending or srv.num_active:
        n = len(srv.trace.steps)
        outs = {r: len(_req(srv, r).out) for r in rids}
        srv.step()
        recs = srv.trace.steps[n:]
        last = {rid: i for i, rid in
                enumerate(recs[0].request_ids if recs else ())}
        for r in rids:
            if len(_req(srv, r).out) > outs[r]:
                logits[r].append(np.asarray(srv.last_logits[last[r]]))
    dims = MLA.dims(c)
    routes = driver._routes(srv.trace, rids, dims)
    return w, srv, rids, logits, routes


def _req(srv, rid):
    for r in list(srv.queue) + [s for s in srv.slots if s is not None]:
        if r.rid == rid:
            return r
    return srv.finished[rid]


def test_server_matches_reference():
    """Prefill, then decode through the paged latent cache: every served
    logits row agrees with the float32 reference fed the same tokens,
    following the served routes."""
    c = copy.deepcopy(TINY_MLA)
    prompts = [[5, 9, 2, 7, 7, 1], [3, 8, 11]]
    w, srv, rids, logits, routes = _serve(c, SEED, prompts, 5)
    seqs = [srv.result(r)[:-1] for r in rids]
    ref, follow = MLA.reference_logits(
        w, seqs, [routes[r][:len(s)] for r, s in zip(rids, seqs)], 0.005)
    for r, p, want in zip(rids, prompts, ref):
        got = np.stack(logits[r])
        np.testing.assert_allclose(got, want[len(p) - 1:len(p) - 1 + len(got)],
                                   rtol=2e-4, atol=2e-4)
    # rows list 0 to k held experts, by published id
    acts = [a for s in srv.trace.steps for a in s.request_activated]
    assert all(len(a) <= 6 and set(a) <= set(range(4)) for a in acts)
    assert any(len(a) == 0 for a in acts) and any(acts)
    assert {s.layer for s in srv.trace.steps} == {1, 2}


@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22])
def test_greedy_choice_is_the_successor(seed):
    """The reference's greedy token after x is succ(x), the next along
    the seeded cycle through the vocabulary, at the published 160-wide
    router and 6 held groups' worth of picks; with the gain at 0 it is
    not."""
    import jax
    c = copy.deepcopy(TINY_MLA)
    c.update(hidden_size=256, vocab_size=2048, n_routed_experts=40)
    c["expert_parallel"]["n_routed_experts"] = 160
    c["program"].update(d_model=256, vocab_size=2048, num_experts=160)
    w = MLA.Weights(c, seed, 1.0)
    V = w.dims.V
    order = np.asarray(jax.random.permutation(
        jax.random.fold_in(w.keys[0], 2), V))
    succ = np.empty(V, np.int64)
    succ[order] = np.roll(order, -1)
    seq = [int(np.random.default_rng(seed).integers(1, V))]
    for _ in range(47):
        seq.append(int(succ[seq[-1]]))
    logits, _ = MLA.reference_logits(w, [seq], None, 0.0)
    assert (np.argmax(logits[0][:-1], -1) == np.asarray(seq[1:])).all()


def test_reference_leaves_out_other_ranks():
    """With no served routes the reference routes by itself; a wrong
    served route (an extra held expert) is not followed, since no near
    tie decides it."""
    c = copy.deepcopy(TINY_MLA)
    w = MLA.Weights(c, SEED, 1.0)
    seq = [4, 8, 15, 16, 23]
    own, f0 = MLA.reference_logits(w, [seq], None, 0.005)
    assert f0["followed_flips"] == 0
    route = np.full((len(seq), 3, 6), -1)
    route[:, 1:, 0] = 0      # expert 0 at every position: not a near tie
    forced, f1 = MLA.reference_logits(w, [seq], [route], 0.005)
    assert f1["followed_flips"] == 0
    np.testing.assert_allclose(forced[0], own[0], rtol=1e-6, atol=1e-6)


def _run(monkeypatch, trace=0, metrics=(), config=TINY_MLA):
    """run.py on the tiny cell, which also reports ``metrics``."""
    import run
    bench = tiny.on_cpu(monkeypatch, config=config)
    for m in bench["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(tiny.cell()["name"])
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "tiny.chat", "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)])
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_rehearsal_is_correct(monkeypatch):
    line = _run(monkeypatch)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["logit_err"]["value"] < 1e-3


NEW_METRICS = ("mla_attn_roofline", "device.idle.dense_ffn_pct",
               "device.idle.shared_pct")


def test_traced_rehearsal_with_the_new_metrics(monkeypatch):
    """A traced run that reports the new cell's three metrics: correct,
    and each reader runs (the CPU has no device plane, so they find
    nothing to read and are left out)."""
    line = _run(monkeypatch, trace=1, metrics=NEW_METRICS)
    assert line["correct"] is True
    assert not set(NEW_METRICS) & set(line["metrics"])
    cell = {"name": "deepseek-v2-236b.batch-8-topic"}
    names = {m["name"] for m in bspec.metrics_for(bspec.benchmark(), cell,
                                                  True)}
    assert names == set(NEW_METRICS)


@pytest.mark.parametrize("fault", ["_altered_token", "_state_unchanged",
                                   "_half_batch"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The tiny MLA cell with the timed path broken as ``test_run``
    breaks it (a token altered where produced, the decoded KV dropped,
    half the batch's expert outputs dropped): ``correct`` is false."""
    import test_run
    getattr(test_run, fault)(monkeypatch)
    line = _run(monkeypatch)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _third_group_skipped(monkeypatch):
    """The served router keeps the groups ranked first, second and
    fourth by their best score, where the published gate keeps the
    first three."""
    from repro.models import moe
    real = moe.route

    def route(logits, cfg, xp=np):
        if xp is not np:
            return real(logits, cfg, xp)
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        g = s.reshape(len(s), cfg.n_group, -1)
        keep = np.zeros(g.shape[:2], bool)
        np.put_along_axis(keep, np.argsort(-g.max(-1), -1)[:, [0, 1, 3]],
                          True, 1)
        pick = np.where(keep[..., None], g, 0.0).reshape(s.shape)
        ids = np.argsort(-pick, -1)[:, :cfg.num_experts_per_tok]
        return np.take_along_axis(s, ids, -1) * cfg.routed_scaling_factor, ids
    monkeypatch.setattr(moe, "route", route)


def test_routing_fault_is_not_correct_at_the_cells_route_tie(monkeypatch):
    """A served router that keeps the wrong third group is not followed
    by the reference at ``deepseek-v2-236b``'s ``route_tie``: the tiny
    MLA cell judged with that tie comes out not correct."""
    c = copy.deepcopy(TINY_MLA)
    c["correct"]["route_tie"] = bspec.config(
        "deepseek-v2-236b")["correct"]["route_tie"]
    _third_group_skipped(monkeypatch)
    line = _run(monkeypatch, config=c)
    assert line["correct"] is False
    assert line["checks"]["logit_err"]["value"] > \
        line["checks"]["logit_err"]["limit"]
