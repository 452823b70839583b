"""Seeded weights and the plain float32 reference of DeepSeek-V2's
decoder: multi-head latent attention (a query LoRA, a compressed KV
latent, a decoupled rope key under YaRN rope scaling), leading dense
SwiGLU layers, then mixture-of-experts layers of shared experts plus
routed experts picked by group-limited greedy routing (softmax over all
experts, the best ``topk_group`` of ``n_group`` groups by their best
score, the top k inside them, weights not renormalised and multiplied by
``routed_scaling_factor``). RMSNorm before each block and the head.

Sizes come from the configuration's published keys (``hidden_size``,
``q_lora_rank``, ``n_group``, ...). This chip is one rank of an
expert-parallel deployment (``expert_parallel`` in the configuration):
it holds the routed experts of ``ranks`` equal slices of the published
ids, whole routing groups, and ``n_routed_experts`` says how many. The
router keeps its published width; the reference, like the program,
computes the held experts' part of each routed sum and leaves out what
the other ranks would add. The weights are laid out as the serving
program takes them (``dense_layers`` ahead of ``layers``) and are made
on the device from the seed, one jitted call per piece; an expert's
weights depend only on the seed, its layer and its published id.

Routing. As in ``gqa_moe``: the embedding carries a shared direction
``u`` and a token-specific unit vector ``t`` in an E-dimensional
subspace ``S`` that no other weight reads or writes, and layer l's
router reads only those, so its logits are a positive scale times
``m[l] + sqrt(E) * Q_l^T t``. Here the offsets make every routing group
equally popular (an eighth of the picks each, as DeepSeek-V2's
device-level balance aims for) and, inside each group, popularity follow
a Zipf law of exponent ``zipf_s`` over its experts: one pattern of
offsets per group, calibrated by simulating the group-limited selection
itself (``group_calibration``), with the ranks shuffled per layer by the
seed inside each group only. A rank holding 2 of 8 groups then takes a
quarter of the picks on every seed. A Zipf law over all experts would
not do: over 160 experts at top-6 its first rank asks more than 1/6 of
the picks, which no expert can take.

Greedy stream: as in ``gqa_moe``, the head adds to each token's column
``SUCCESSOR_GAIN`` times a vector of its predecessor along one seeded
cycle through the vocabulary, so the greedy token after x is succ(x);
here that vector is the predecessor's routing vector in ``S``, which the
layers never write (``_make_top``).

The reference imports nothing of the program. It recomputes the same
weights from the seed, bit for bit (the same jitted calls), then runs
one layer at a time and one held expert at a time at "highest" matmul
precision. Where a served routing choice differs from the reference's
and the reference's own choice is decided by a near tie (within ``tie``
of a router logit, in the group scores or in the experts' order), the
reference follows the served choice.
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import spec as bspec  # noqa: E402

_G = bspec.load_module(Path(__file__).with_name("gqa_moe.py"),
                       "bench_model_gqa_moe")

F32 = jnp.float32
PAD = _G.PAD


class Dims(NamedTuple):
    d: int
    H: int
    hd: int       # nope and value head width
    rd: int       # rope key width
    r: int        # kv_lora_rank
    rq: int       # q_lora_rank
    F: int        # routed expert width
    Fd: int       # dense layers' width
    Fs: int       # the shared experts' summed width
    E: int        # published routed experts (the router's width)
    k: int
    G: int        # n_group
    Gk: int       # topk_group
    V: int
    L: int
    nd: int       # leading dense layers
    theta: float
    eps: float
    scale: float  # routed_scaling_factor
    yarn: Tuple   # rope_scaling as sorted (key, value) pairs
    dtype: str


def dims(c: dict) -> Dims:
    assert c["v_head_dim"] == c["qk_nope_head_dim"]
    assert c["topk_method"] == "group_limited_greedy"
    assert not c["norm_topk_prob"]
    return Dims(c["hidden_size"], c["num_attention_heads"],
                c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                c["kv_lora_rank"], c["q_lora_rank"],
                c["moe_intermediate_size"], c["intermediate_size"],
                c["n_shared_experts"] * c["moe_intermediate_size"],
                c["expert_parallel"]["n_routed_experts"],
                c["num_experts_per_tok"], c["n_group"], c["topk_group"],
                c["vocab_size"], c["num_hidden_layers"],
                c["first_k_dense_replace"], float(c["rope_theta"]),
                float(c["rms_norm_eps"]), float(c["routed_scaling_factor"]),
                tuple(sorted(c["rope_scaling"].items())),
                c["program"]["dtype"])


def layout(c: dict):
    """The layers with routed experts, this rank's published ids (whole
    groups) and the published count."""
    m = dims(c)
    ep = c["expert_parallel"]
    per = m.E // ep["ranks"]
    held = list(range(ep["rank"] * per, (ep["rank"] + 1) * per))
    assert len(held) == c["n_routed_experts"] and per % (m.E // m.G) == 0
    return list(range(m.nd, m.L)), held, m.E


# ----------------------------------------------------- operation counts
def mla_attend_call(rows: int, blocks: int, block_size: int, m: Dims, *,
                    kv_bytes: int = 2, q_bytes: int = 2):
    """The MLA decode attention core (``mla_attend_paged``) of ``rows``
    query rows, each over ``blocks`` blocks of ``block_size`` positions
    of its latent (r) and rope key (rd): the scores over both, the
    context in the latent space. Reads each row's strip, its queries and
    table, writes the fp32 context. Returns (flops, bytes)."""
    keys = rows * blocks * block_size
    fl = 2 * m.H * keys * (m.r + m.rd) + 2 * m.H * keys * m.r
    nbytes = (keys * (m.r + m.rd) * kv_bytes
              + rows * m.H * (m.r + m.rd) * q_bytes
              + rows * m.H * m.r * 4 + 4 * rows * blocks)
    return fl, nbytes


def token_flops(c: dict, context: float) -> float:
    """Model FLOPs of one token at ``context`` earlier positions on this
    rank: the MLA projections (queries absorbed through ``w_kb``),
    attention in the latent space, the dense layers, and in each MoE
    layer the router, the shared experts and this rank's share of the k
    routed experts (k x held / E), then the head."""
    m = dims(c)
    held = len(layout(c)[1])
    proj = 2 * (m.d * m.rq + m.rq * m.H * (m.hd + m.rd) + m.d * (m.r + m.rd)
                + m.H * m.hd * m.r * 2 + m.H * m.hd * m.d)
    attend = 2 * m.H * context * (2 * m.r + m.rd)
    dense = 3 * 2 * m.d * m.Fd
    moe = (2 * m.d * m.E + 3 * 2 * m.d * m.Fs
           + m.k * held / m.E * 3 * 2 * m.d * m.F)
    return (m.L * (proj + attend) + m.nd * dense + (m.L - m.nd) * moe
            + 2 * m.d * m.V)


# ---------------------------------------------------------------- skew
def group_select(z: np.ndarray, G: int, Gk: int, k: int) -> np.ndarray:
    """Ids [n, k] that group-limited greedy routing picks from scores
    z [n, E]: the top k inside the Gk groups of highest best score."""
    n, E = z.shape
    grouped = z.reshape(n, G, E // G)
    top_g = np.argpartition(-grouped.max(-1), Gk - 1, axis=1)[:, :Gk]
    keep = np.zeros((n, G), bool)
    np.put_along_axis(keep, top_g, True, 1)
    masked = np.where(keep[:, :, None], grouped, -np.inf).reshape(n, E)
    return np.argpartition(-masked, k - 1, axis=1)[:, :k]


@functools.lru_cache(maxsize=4)
def group_calibration(E: int, G: int, Gk: int, k: int, s: float,
                      n: int = 8192, iters: int = 100) -> np.ndarray:
    """Offsets [E / G], most popular first, such that with every group
    given this pattern, group-limited greedy routing of ``pattern + z``
    (z = sqrt(E) times a uniform unit vector) picks the r-th of a
    group's experts with share ~ (r+1)^-s / H of the group's picks, and
    each group (by symmetry) an eighth of all picks. Fixed Monte Carlo
    draws: the same offsets for every seed."""
    per = E // G
    p = np.arange(1, per + 1, dtype=np.float64) ** -s
    p /= p.sum()
    rng = np.random.default_rng(0)
    z = rng.standard_normal((n, E))
    z *= math.sqrt(E) / np.linalg.norm(z, axis=1, keepdims=True)
    base = np.log(p) - np.log(p).mean()
    for _ in range(iters):
        top = group_select(np.tile(base, G)[None] + z, G, Gk, k)
        share = np.bincount(top.ravel() % per, minlength=per) / (n * k)
        base += 0.5 * (np.log(p) - np.log(np.maximum(share, 1e-6)))
    return base


def zipf_offsets(c: dict, seed: int, zipf_s: float) -> np.ndarray:
    """m [L, E]: each MoE layer's offsets, the calibrated pattern in
    every group with its ranks shuffled per layer and group by the seed
    (all 0 for zipf_s == 0, and for the dense layers)."""
    m = dims(c)
    out = np.zeros((m.L, m.E), np.float32)
    if zipf_s == 0:
        return out
    base = group_calibration(m.E, m.G, m.Gk, m.k, float(zipf_s))
    per = m.E // m.G
    rng = np.random.default_rng([seed, 3])
    for l in range(m.nd, m.L):
        for g in range(m.G):
            out[l, g * per + rng.permutation(per)] = base
    return out


# ------------------------------------------------------------- weights
def _keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 4)  # top, layers, experts, skew


@functools.partial(jax.jit, static_argnums=0)
def _basis(m: Dims, kb):
    """[d, 1 + E] orthonormal: u, then the token subspace S."""
    g = jax.random.normal(jax.random.fold_in(kb, 0), (m.d, 1 + m.E), F32)
    return jnp.linalg.qr(g)[0]


@functools.partial(jax.jit, static_argnums=0)
def _make_top(m: Dims, kt, kb, W):
    """Embedding (with the routing components), final norm and the
    successor head. The head reads the predecessor's routing vector in
    S, which no layer writes, so the greedy token after x is succ(x)
    whatever the layers add: MLA's latent and query norms give its
    attention outputs unit scale, which would swamp a head that read the
    embedding's random part (``gqa_moe``'s)."""
    k_emb, k_out = jax.random.split(kt)
    amp = _G.SKEW_NORM * math.sqrt(m.d)
    embed = _G._proj_out(jax.random.normal(k_emb, (m.V, m.d), F32) * 0.02, W)
    unembed = jax.random.normal(k_out, (m.d, m.V), F32) * 0.02
    order = jax.random.permutation(jax.random.fold_in(kt, 2), m.V)
    pred = jnp.zeros((m.V,), jnp.int32).at[order].set(jnp.roll(order, 1))
    t = jax.random.normal(jax.random.fold_in(kb, 1), (m.V, m.E), F32)
    t = t / jnp.linalg.norm(t, axis=1, keepdims=True)
    unembed = (_G._proj_in(unembed, W)
               + _G.SUCCESSOR_GAIN * W[:, 1:] @ t[pred].T)
    embed = embed + amp * W[:, 0][None, :] + amp * t @ W[:, 1:].T
    dt = jnp.dtype(m.dtype)
    return {"embed": embed.astype(dt), "final_norm": jnp.ones((m.d,), dt),
            "unembed": unembed.astype(dt)}


def _swiglu_init(key, m: Dims, width: int, W):
    k1, k3, k2 = jax.random.split(key, 3)
    res = 1.0 / math.sqrt(2 * m.L)
    return {"w1": _G._proj_in(_G._tn(k1, (m.d, width), m.d), W),
            "w3": _G._proj_in(_G._tn(k3, (m.d, width), m.d), W),
            "w2": _G._proj_out(_G._tn(k2, (width, m.d), width, res), W)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_layer(m: Dims, moe: bool, kl, kb, W, offsets, l):
    """Layer l's non-expert weights: norms, MLA, and the dense FFN or
    the router and shared experts."""
    ka, kf = jax.random.split(jax.random.fold_in(kl, l))
    ks = jax.random.split(ka, 7)
    res = 1.0 / math.sqrt(2 * m.L)
    a = {"w_qa": _G._proj_in(_G._tn(ks[0], (m.d, m.rq), m.d), W),
         "q_norm": jnp.ones((m.rq,), F32),
         "w_qb": _G._tn(ks[1], (m.rq, m.H, m.hd + m.rd), m.rq),
         "w_dkv": _G._proj_in(_G._tn(ks[2], (m.d, m.r), m.d), W),
         "w_kr": _G._proj_in(_G._tn(ks[3], (m.d, m.rd), m.d), W),
         "latent_norm": jnp.ones((m.r,), F32),
         "w_kb": _G._tn(ks[4], (m.r, m.H, m.hd), m.r),
         "w_vb": _G._tn(ks[5], (m.r, m.H, m.hd), m.r),
         "wo": _G._proj_out(_G._tn(ks[6], (m.H, m.hd, m.d), m.H * m.hd,
                                   res), W)}
    dt = jnp.dtype(m.dtype)
    out = {"ln1": jnp.ones((m.d,), dt), "ln2": jnp.ones((m.d,), dt),
           "attn": {n: v.astype(dt) for n, v in a.items()}}
    if not moe:
        out["mlp"] = {n: v.astype(dt) for n, v in
                      _swiglu_init(kf, m, m.Fd, W).items()}
        return out
    amp = _G.SKEW_NORM * math.sqrt(m.d)
    q = jnp.linalg.qr(jax.random.normal(
        jax.random.fold_in(kb, 2 + l), (m.E, m.E), F32))[0]
    router = (W[:, :1] * offsets[None, :] / (amp * _G.ROUTER_GAIN)
              + W[:, 1:] @ q * (math.sqrt(m.E) / (amp * _G.ROUTER_GAIN)))
    out["moe"] = {"router": router,
                  "shared": {n: v.astype(dt) for n, v in
                             _swiglu_init(kf, m, m.Fs, W).items()}}
    return out


@functools.partial(jax.jit, static_argnums=0)
def _make_experts(m: Dims, key, W, ids):
    """The experts of published ids ``ids`` of one layer, one at a time
    (each from the layer's key folded with its id): w1, w3 [n, d, F],
    w2 [n, F, d]."""
    def one(e):
        w = _swiglu_init(jax.random.fold_in(key, e), m, m.F, W)
        return {n: v.astype(jnp.dtype(m.dtype)) for n, v in w.items()}
    return jax.lax.map(one, ids)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class Weights:
    """The cell's weights from the seed, made on the device in the type
    they are served in (the configuration's; the router fp32)."""

    def __init__(self, c: dict, seed: int, zipf_s: float):
        self.dims = dims(c)
        self.held = np.asarray(layout(c)[1], np.int32)
        self.keys = _keys(seed)
        self.W = _basis(self.dims, self.keys[3])
        self.offsets = jnp.asarray(zipf_offsets(c, seed, zipf_s))

    def params(self):
        """The non-expert tree as the program takes it."""
        m, (kt, kl, _, kb) = self.dims, self.keys
        out = _make_top(m, kt, kb, self.W)
        for name, moe, ls in (("dense_layers", False, range(m.nd)),
                              ("layers", True, range(m.nd, m.L))):
            if ls:
                out[name] = _stack([_make_layer(m, moe, kl, kb, self.W,
                                                self.offsets[l], l)
                                    for l in ls])
        return out

    def experts(self, layer: int):
        """Layer ``layer``'s held experts stacked on the device, in the
        order of ``layout``: w1, w3 [n, d, F] and w2 [n, F, d]."""
        key = jax.random.fold_in(self.keys[2], layer)
        return _make_experts(self.dims, key, self.W, jnp.asarray(self.held))


# ----------------------------------------------------------- reference
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(m: Dims):
    """DeepSeek-V2's YaRN rotary embedding: (inv_freq [rd / 2], the
    factor on cos and sin, the attention's softmax scale)."""
    y = dict(m.yarn)
    dim, base = m.rd, m.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (y["factor"] * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float64) / dim))

    def corr_dim(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = inter * (1.0 - mask) + extra * mask
    cs = (_yarn_mscale(y["factor"], y["mscale"])
          / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    scale = ((m.hd + m.rd) ** -0.5
             * _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2)
    return inv_freq.astype(np.float32), cs, scale


def _rope(x, pos, inv_freq, cs):
    """Split-half rotation of x [S, ..., rd] at positions pos [S]."""
    half = x.shape[-1] // 2
    ang = pos.reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * inv_freq
    c, s = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


_q8, _a8, _rms = _G._q8, _G._a8, _G._rms


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(m: Dims, fp8: bool, stack, i):
    p = jax.tree.map(lambda x: x[i].astype(F32), stack)
    if fp8:
        a = p["attn"]
        for n, ax in (("w_qa", 0), ("w_qb", 0), ("w_dkv", 0), ("w_kr", 0),
                      ("w_kb", 0), ("w_vb", 0), ("wo", (0, 1))):
            a[n] = _q8(a[n], ax)
        if "moe" in p:
            p["moe"]["router"] = _q8(p["moe"]["router"], 0)
    return p


@functools.partial(jax.jit, static_argnums=(0, 1))
def _top_weights(m: Dims, fp8: bool, params):
    emb = params["embed"].astype(F32)
    out = params["unembed"].astype(F32)
    if fp8:
        emb, out = _q8(emb, 1), _q8(out, 0)
    return {"embed": emb, "unembed": out,
            "final_norm": params["final_norm"].astype(F32)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(m: Dims, fp8: bool, p, h):
    """h [S, d] through one layer's latent attention (causal, every
    position from 0), residual added."""
    S = h.shape[0]
    pos = jnp.arange(S, dtype=F32)
    inv_freq, cs, scale = yarn(m)
    a = p["attn"]
    x = _a8(_rms(h, p["ln1"], m.eps), fp8)
    qa = _a8(_rms(x @ a["w_qa"], a["q_norm"], m.eps), fp8)
    q = jnp.einsum("sr,rhk->shk", qa, a["w_qb"])
    q_nope, q_rope = q[..., :m.hd], _rope(q[..., m.hd:], pos, inv_freq, cs)
    latent = _a8(_rms(x @ a["w_dkv"], a["latent_norm"], m.eps), fp8)
    k_rope = _rope(x @ a["w_kr"], pos, inv_freq, cs)
    k_nope = jnp.einsum("sr,rhk->shk", latent, a["w_kb"])
    v = jnp.einsum("sr,rhk->shk", latent, a["w_vb"])
    s = (jnp.einsum("shk,thk->hst", q_nope, k_nope)
         + jnp.einsum("shk,tk->hst", q_rope, k_rope)) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v)
    o = _a8(o.reshape(S, -1), fp8).reshape(o.shape)
    return h + jnp.einsum("shk,hkd->sd", o, a["wo"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _route(m: Dims, fp8: bool, p, h, held, served, tie, follow):
    """One MoE layer's router over h [S, d]: the FFN input x, and the
    gate of each held expert [S, n] (its softmax score times the
    scaling factor where picked, else 0). ``served`` [S, n] marks the
    served picks among the held experts; the reference follows them
    where each difference from its own group-limited top-k is a near
    tie: an expert picked only by the served row trails the third
    group's best score (if its group was not kept) plus the k-th kept
    score by at most ``tie`` in all; one picked only by the reference
    leads the (k+1)-th kept score, or its group's best leads the fourth
    group's, by at most ``tie``. Also returns, per position, whether
    the served choice differs, its margin (the largest of those
    differences) and whether it was followed."""
    x = _a8(_rms(h, p["ln2"], m.eps), fp8)
    z = x @ p["moe"]["router"]                               # [S, E]
    S, per = z.shape[0], m.E // m.G
    gbest = z.reshape(S, m.G, per).max(-1)                   # [S, G]
    gs = jnp.sort(gbest, -1)[:, ::-1]
    kept = gbest >= gs[:, m.Gk - 1:m.Gk]                     # [S, G]
    cand = jnp.where(jnp.repeat(kept, per, -1), z, -jnp.inf)
    cs = jnp.sort(cand, -1)[:, ::-1]
    kth, nxt = cs[:, m.k - 1:m.k], cs[:, m.k:m.k + 1]
    ref = cand >= kth                                        # [S, E]
    grp = held // per
    zh, gh = z[:, held], gbest[:, grp]
    add = (jnp.maximum(gs[:, m.Gk - 1:m.Gk] - gh, 0.0)
           + jnp.maximum(kth - zh, 0.0))
    drop = jnp.maximum(jnp.minimum(zh - nxt, gh - gs[:, m.Gk:m.Gk + 1]), 0.0)
    mine = ref[:, held]
    cost = jnp.where(served & ~mine, add, jnp.where(mine & ~served, drop, 0.0))
    worst = cost.max(-1)
    differs = jnp.any(served != mine, -1)
    use = follow & (worst <= tie)
    chosen = jnp.where(use[:, None], served, mine)
    score = jax.nn.softmax(z, -1)[:, held] * m.scale
    gates = jnp.where(chosen, score, 0.0)
    return x, gates, differs, worst, use


@functools.partial(jax.jit, static_argnums=0)
def _ffn(fp8: bool, h, x, w1, w3, w2, gate):
    """h plus gate [S] times one SwiGLU (an expert, the shared experts
    or a dense FFN) of x."""
    w1, w3, w2 = (w.astype(F32) for w in (w1, w3, w2))
    if fp8:
        w1, w3, w2 = _q8(w1, 0), _q8(w3, 0), _q8(w2, 0)
    y = _a8(jax.nn.silu(x @ w1) * (x @ w3), fp8) @ w2
    return h + gate[:, None] * y


@functools.partial(jax.jit, static_argnums=(0, 1))
def _norm2(m: Dims, fp8: bool, p, h):
    return _a8(_rms(h, p["ln2"], m.eps), fp8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(m: Dims, fp8: bool, top, h):
    return _a8(_rms(h, top["final_norm"], m.eps), fp8) @ top["unembed"]


def reference_logits(weights: Weights, seqs: Sequence[Sequence[int]],
                     served: Optional[Sequence[np.ndarray]], tie: float,
                     *, fp8: bool = False):
    """Logits [S_i, V] (host, fp32) of each token sequence fed from
    position 0, and the routing-follow counts: how many positions
    followed a served choice that differs and the largest margin among
    them, and the margin of every position whose served choice differs,
    followed or not (``differing_margins``, ascending).

    ``served[i]`` [S_i, L, k] holds the served held experts of sequence
    i at each position and layer by published id, -1 padding a row
    that lists fewer than k (follow them within ``tie``); None, or
    ``fp8=True`` for the control, routes by the reference's own
    choice. With ``fp8`` every matmul takes its inputs rounded through
    float8_e4m3fn: weights per output channel, activations per row."""
    m = weights.dims
    held = weights.held
    follow = served is not None and not fp8
    lens = [len(s) for s in seqs]
    col = {int(e): i for i, e in enumerate(held)}
    # made as the program's were, outside the "highest" context (see
    # gqa_moe.reference_logits)
    highest = functools.partial(jax.default_matmul_precision, "highest")
    params = weights.params()
    top = _top_weights(m, fp8, params)
    del params["embed"], params["unembed"]   # 2.1 GB the layers never read
    hs, masks = [], []
    for i, seq in enumerate(seqs):
        S = -(-lens[i] // PAD) * PAD
        toks = np.zeros(S, np.int32)
        toks[:lens[i]] = seq
        hs.append(top["embed"][jnp.asarray(toks)])
        mask = np.zeros((m.L, S, len(held)), bool)
        if follow:
            r = np.asarray(served[i])
            for t, l, e in zip(*np.nonzero(r >= 0)):
                mask[l, t, col[int(r[t, l, e])]] = True
        masks.append(mask)
    followed, margins = [], []
    held_d = jnp.asarray(held)
    for l in range(m.L):
        stack, i_l = ((params["dense_layers"], l) if l < m.nd
                      else (params["layers"], l - m.nd))
        p = _layer_weights(m, fp8, stack, i_l)
        ex = weights.experts(l) if l >= m.nd else None
        with highest():
            for i in range(len(seqs)):
                hs[i] = _attention(m, fp8, p, hs[i])
            if ex is None:
                for i in range(len(seqs)):
                    x = _norm2(m, fp8, p, hs[i])
                    hs[i] = _ffn(fp8, hs[i], x, p["mlp"]["w1"], p["mlp"]["w3"],
                                 p["mlp"]["w2"], jnp.ones((x.shape[0],), F32))
                continue
            gates = []
            for i in range(len(seqs)):
                x, g, dif, mg, use = _route(m, fp8, p, hs[i], held_d,
                                            jnp.asarray(masks[i][l]), tie,
                                            follow)
                gates.append((x, g))
                if follow:
                    dif = np.asarray(dif)[:lens[i]]
                    margins += np.asarray(mg)[:lens[i]][dif].tolist()
                    followed += np.asarray(mg)[:lens[i]][
                        dif & np.asarray(use)[:lens[i]]].tolist()
                sh = p["moe"]["shared"]
                hs[i] = _ffn(fp8, hs[i], x, sh["w1"], sh["w3"], sh["w2"],
                             jnp.ones((x.shape[0],), F32))
            for e in range(len(held)):
                for i, (x, g) in enumerate(gates):
                    # waiting for each keeps one expert's fp32 weights on
                    # the device, not a layer's
                    hs[i] = jax.block_until_ready(_ffn(
                        fp8, hs[i], x, ex["w1"][e], ex["w3"][e], ex["w2"][e],
                        g[:, e]))
        del ex, p
    del params
    with highest():
        out = [np.asarray(_head(m, fp8, top, h))[:n]
               for h, n in zip(hs, lens)]
    return out, {"followed_flips": len(followed),
                 "followed_max_margin": max(followed, default=0.0),
                 "differing_margins": sorted(margins)}
