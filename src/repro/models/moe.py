"""Mixture-of-Experts layer.

Three compute paths over one parameter layout:

* ``moe_dense``   — every expert on every token, gate-weighted. Exact;
  used for tiny smoke models and as the oracle in tests.
* ``moe_capacity`` — GShard/MaxText-style capacity-bounded scatter
  dispatch (tokens above capacity drop). This is the distributed path:
  expert dim shards over the "model" mesh axis (expert parallelism; the
  SPMD partitioner materialises the all-to-alls), capacity dim over
  "data".
* the offload path lives in ``repro.core.offload_engine`` and reuses the
  same per-expert weights, streaming them through the expert cache.

Routing: one router (``route``) for every path, on the device or on
the host: softmax top-k with renormalisation (Mixtral convention), or
DeepSeek-V2's group-limited top-k scaled without renormalisation; plus
the standard load-balance auxiliary loss (Shazeer 2017 / GShard).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init
from repro.models.sharding import active_mesh, active_rules, constrain


def init_moe(key, cfg, dtype):
    d, ff, E = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    res_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": dense_init(ks[0], (d, E), d, dtype=jnp.float32),
        "experts": {
            "w1": dense_init(ks[1], (E, d, ff), d, dtype=dtype),
            "w3": dense_init(ks[2], (E, d, ff), d, dtype=dtype),
            "w2": dense_init(ks[3], (E, ff, d), ff, scale=res_scale, dtype=dtype),
        },
    }
    if cfg.num_shared_experts:
        sff = ff * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w1": dense_init(k1, (d, sff), d, dtype=dtype),
            "w3": dense_init(k2, (d, sff), d, dtype=dtype),
            "w2": dense_init(k3, (sff, d), sff, scale=res_scale, dtype=dtype),
        }
    return p


def _softmax(x, xp):
    if xp is np:
        e = np.exp(x - x.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)
    return jax.nn.softmax(x, axis=-1)


def _top_k_ids(x, k: int, xp):
    """Indices of the k largest entries along the last axis."""
    if xp is np:
        return np.argsort(-x, axis=-1)[..., :k]
    return jax.lax.top_k(x, k)[1]


def route(logits, cfg, xp=jnp):
    """The router's choice from its fp32 logits [..., E]: (weights
    [..., k], ids [..., k]). ``xp`` is ``jnp`` on the device or ``np``
    on the host (``OffloadEngine._route``); both compute the same.

    ``topk_method`` "greedy" with ``norm_topk_prob`` is the softmax over
    the k largest logits (Mixtral; the renormalised top-k of the full
    softmax). Otherwise the scores are the softmax over all E logits;
    "group_limited_greedy" (DeepSeek-V2) splits the E experts into
    ``n_group`` equal groups, keeps the ``topk_group`` groups whose best
    score is highest and takes the top k inside them. The k scores are
    then renormalised (``norm_topk_prob``) or multiplied by
    ``routed_scaling_factor``."""
    k = cfg.num_experts_per_tok
    if cfg.topk_method == "greedy" and cfg.norm_topk_prob:
        ids = _top_k_ids(logits, k, xp)
        return _softmax(xp.take_along_axis(logits, ids, axis=-1), xp), ids
    scores = _softmax(logits, xp)
    pick = scores
    if cfg.topk_method == "group_limited_greedy":
        grouped = scores.reshape(*scores.shape[:-1], cfg.n_group, -1)
        best = _top_k_ids(grouped.max(axis=-1), cfg.topk_group, xp)
        keep = (xp.arange(cfg.n_group) == best[..., None]).any(axis=-2)
        pick = xp.where(keep[..., None], grouped, 0.0).reshape(scores.shape)
    elif cfg.topk_method != "greedy":
        raise ValueError(f"unknown topk_method {cfg.topk_method!r}")
    ids = _top_k_ids(pick, k, xp)
    w = xp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        return w / (w.sum(axis=-1, keepdims=True) + 1e-20), ids
    return w * cfg.routed_scaling_factor, ids


def router_probs(p, cfg, x):
    """x [..., d] -> (gate_logits [..., E], topk probs [..., k], ids [..., k])."""
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    top_probs, top_ids = route(logits, cfg)
    return logits, top_probs, top_ids


def load_balance_loss(logits, top_ids, num_experts: int) -> jnp.ndarray:
    """GShard aux loss: E * mean_e(frac_tokens_e * mean_prob_e)."""
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs.reshape(-1, num_experts)
    ids = top_ids.reshape(-1, top_ids.shape[-1])
    sel = jax.nn.one_hot(ids[:, 0], num_experts, dtype=jnp.float32)
    frac_tokens = sel.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    return num_experts * jnp.sum(frac_tokens * mean_prob)


def _swiglu_experts(experts, x_e):
    """x_e [E, C, d] through stacked expert SwiGLU -> [E, C, d]."""
    h = jnp.einsum("ecd,edf->ecf", x_e, experts["w1"])
    g = jnp.einsum("ecd,edf->ecf", x_e, experts["w3"])
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, experts["w2"])


def _shared_out(p, x):
    if "shared" not in p:
        return 0.0
    s = p["shared"]
    return (jax.nn.silu(x @ s["w1"]) * (x @ s["w3"])) @ s["w2"]


def moe_dense(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact all-experts path. x [B,S,d] -> (y, aux_loss)."""
    B, S, d = x.shape
    logits, top_probs, top_ids = router_probs(p, cfg, x)
    E = cfg.num_experts
    # every expert on every token
    h = jnp.einsum("bsd,edf->bsef", x, p["experts"]["w1"])
    g = jnp.einsum("bsd,edf->bsef", x, p["experts"]["w3"])
    out_e = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * g, p["experts"]["w2"])
    gates = jnp.zeros((B, S, E), jnp.float32)
    bidx = jnp.arange(B)[:, None, None]
    sidx = jnp.arange(S)[None, :, None]
    gates = gates.at[bidx, sidx, top_ids].set(top_probs)
    y = jnp.einsum("bsed,bse->bsd", out_e.astype(jnp.float32), gates)
    y = y.astype(x.dtype) + _shared_out(p, x)
    aux = load_balance_loss(logits, top_ids, E)
    return y, aux


def moe_capacity(p, cfg, x, *, capacity_factor: Optional[float] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-bounded scatter dispatch. x [B,S,d] -> (y, aux_loss).

    Flattens tokens, computes position-in-expert by one-hot cumsum,
    scatters into a [E, C, d] buffer (drops overflow), runs the stacked
    expert FFN, gathers back with gate weighting.
    """
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    C = max(int(math.ceil(T * k * cf / E)), 8)
    # MXU-friendly capacity
    C = -(-C // 8) * 8

    logits, top_probs, top_ids = router_probs(p, cfg, x)
    aux = load_balance_loss(logits, top_ids, E)

    xf = x.reshape(T, d)
    fid = top_ids.reshape(T * k)                 # flat expert ids
    fp = top_probs.reshape(T * k)
    fid = constrain(fid, "batch")
    fp = constrain(fp, "batch")

    oh = jax.nn.one_hot(fid, E, dtype=jnp.int32)          # [T*k, E]
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1  # [T*k]
    keep = pos < C
    slot = jnp.where(keep, fid * C + pos, E * C)          # overflow -> dump row

    x_rep = jnp.repeat(xf, k, axis=0)                     # [T*k, d]
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].add(x_rep, mode="drop")
    x_e = buf[:E * C].reshape(E, C, d)
    x_e = constrain(x_e, "experts", "capacity", None)

    out_e = _swiglu_experts(p["experts"], x_e)
    out_e = constrain(out_e, "experts", "capacity", None)

    out_flat = out_e.reshape(E * C, d)
    gathered = jnp.where(keep[:, None],
                         out_flat.at[jnp.minimum(slot, E * C - 1)].get(
                             mode="clip"), 0.0)
    y = (gathered.astype(jnp.float32) * fp[:, None]).reshape(T, k, d).sum(axis=1)
    y = y.astype(x.dtype).reshape(B, S, d)
    y = y + _shared_out(p, x)
    return y, aux


def moe_gather(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weight-gather path for tiny token counts (decode with small batch).

    Gathers only the selected experts' weights ([T,k,d,ff] slices), so
    both FLOPs *and* bytes match the k-active-experts reality — the
    capacity path would read every expert's weights, overstating decode
    memory traffic by E/k.
    """
    B, S, d = x.shape
    T = B * S
    logits, top_probs, top_ids = router_probs(p, cfg, x)
    aux = load_balance_loss(logits, top_ids, cfg.num_experts)
    xf = x.reshape(T, d)
    ids = top_ids.reshape(T, -1)                       # [T, k]
    w1 = p["experts"]["w1"][ids]                       # [T, k, d, ff]
    w3 = p["experts"]["w3"][ids]
    w2 = p["experts"]["w2"][ids]                       # [T, k, ff, d]
    h = jnp.einsum("td,tkdf->tkf", xf, w1)
    g = jnp.einsum("td,tkdf->tkf", xf, w3)
    out = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(h) * g, w2)
    probs = top_probs.reshape(T, -1)
    y = jnp.einsum("tkd,tk->td", out.astype(jnp.float32), probs)
    y = y.astype(x.dtype).reshape(B, S, d) + _shared_out(p, x)
    return y, aux


def _dispatch_local(cfg, xf, top_probs, top_ids, capacity: int):
    """Local (per-shard) capacity dispatch. xf [T,d] -> buf [E,C,d] plus
    the (slot, keep, probs) needed to gather back."""
    T, d = xf.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = capacity
    fid = top_ids.reshape(T * k)
    fp = top_probs.reshape(T * k)
    oh = jax.nn.one_hot(fid, E, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
    keep = pos < C
    slot = jnp.where(keep, fid * C + pos, E * C)
    x_rep = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].add(x_rep, mode="drop")
    return buf[:E * C].reshape(E, C, d), slot, keep, fp


def moe_ep_shardmap(p, cfg, x, *, capacity_factor: Optional[float] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with EXPLICIT all-to-alls via shard_map.

    The pjit scatter path (``moe_capacity``) leaves dispatch to the SPMD
    partitioner, which materialises full-activation all-reduces
    ([T·k, d] fp32 per MoE layer — §Perf measured 324 s of collective
    time per train step on jamba-398B). Here dispatch is local to each
    (pod, data) shard and only the [E, C_loc, d] expert buffers cross
    the ICI, twice, as true all-to-alls over the "model" axis that owns
    the experts.

    Requires E % model_axis == 0 (the EP regime) and an active mesh.
    """
    mesh = active_mesh()
    rules = active_rules()
    model_ax = rules.get("model")
    b_rule = rules.get("batch")
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    ep = mesh.shape[model_ax]
    assert E % ep == 0, (E, ep)
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor

    b_axes = tuple(b_rule) if isinstance(b_rule, (tuple, list)) else (
        (b_rule,) if b_rule else ())
    n_data = 1
    for a in b_axes:
        n_data *= mesh.shape[a]
    # tokens are sharded over batch axes AND the sequence over the model
    # axis — every rank dispatches a disjoint token slice (dispatching
    # model-replicated tokens would all-to-all 16 duplicate copies).
    assert S % ep == 0, (S, ep)
    T_rank = (B // n_data) * (S // ep)
    C = max(int(math.ceil(T_rank * k * cf / E)), 8)
    C = -(-C // 8) * 8

    from jax.sharding import PartitionSpec as P

    def local_fn(xl, router, w1, w3, w2, shared):
        Bl, Sl, dl = xl.shape
        xf = xl.reshape(Bl * Sl, dl)
        logits = (xf.astype(jnp.float32) @ router)
        top_probs, top_ids = route(logits, cfg)

        buf, slot, keep, fp = _dispatch_local(cfg, xf, top_probs, top_ids, C)
        # [E, C, d] -> exchange expert shards: [E/ep, C*ep, d]
        buf = jax.lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=1,
                                 tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, w1)
        g = jnp.einsum("ecd,edf->ecf", buf, w3)
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2)
        out = jax.lax.all_to_all(out, model_ax, split_axis=1, concat_axis=0,
                                 tiled=True)            # back to [E, C, d]
        out_flat = out.reshape(E * C, dl)
        gathered = jnp.where(
            keep[:, None],
            out_flat.at[jnp.minimum(slot, E * C - 1)].get(mode="clip"), 0.0)
        y = (gathered.astype(jnp.float32) * fp[:, None]) \
            .reshape(Bl * Sl, k, dl).sum(axis=1).astype(xl.dtype)
        y = y.reshape(Bl, Sl, dl)
        if shared is not None:
            y = y + (jax.nn.silu(xl @ shared["w1"]) * (xl @ shared["w3"])) \
                @ shared["w2"]
        aux = load_balance_loss(logits.reshape(Bl, Sl, E), top_ids, E)
        aux = jax.lax.pmean(aux, b_axes + (model_ax,))
        return y, aux

    shared = p.get("shared")
    bspec = b_rule if b_rule else None
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, model_ax, None), P(), P(model_ax, None, None),
                  P(model_ax, None, None), P(model_ax, None, None),
                  P()),
        out_specs=(P(bspec, model_ax, None), P()),
        check_vma=False,
    )(x, p["router"], p["experts"]["w1"], p["experts"]["w3"],
      p["experts"]["w2"], shared)
    return y, aux


def moe_apply(p, cfg, x, *, path: str = "auto"):
    """path: 'dense' | 'capacity' | 'gather' | 'auto'."""
    if path == "dense":
        return moe_dense(p, cfg, x)
    if path == "capacity":
        return moe_capacity(p, cfg, x)
    if path == "gather":
        return moe_gather(p, cfg, x)
    if path == "ep":
        return moe_ep_shardmap(p, cfg, x)
    # auto
    T = x.shape[0] * x.shape[1]
    if T <= 256 and cfg.num_experts <= 8:
        return moe_dense(p, cfg, x)
    if T * cfg.num_experts_per_tok <= cfg.num_experts:
        return moe_gather(p, cfg, x)
    mesh = active_mesh()
    if (mesh is not None and active_rules().get("experts_mode") == "ep"
            and active_rules().get("moe_shardmap", True)):
        ep = mesh.shape[active_rules().get("model")]
        if T >= 4096 and x.shape[1] % ep == 0:
            return moe_ep_shardmap(p, cfg, x)
    return moe_capacity(p, cfg, x)
