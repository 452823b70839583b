"""Seeded weights and the plain float32 reference of a decoder whose
every layer is grouped-query attention (RoPE, split-half) followed by a
top-k routed SwiGLU mixture of experts, Mixtral-style: softmax over the
k chosen router logits, RMSNorm before each block and before the head.

Sizes come from the configuration's published keys (``hidden_size``,
``num_attention_heads``, ...). The weights are laid out as the serving
program takes them, and are made on the device from the seed: the
non-expert tree in one jitted call, each layer's experts in another.

Routing. The router, the embedding and the head are rebuilt so that
each layer's expert popularity follows a Zipf law of exponent
``zipf_s`` (expert ranks shuffled per layer by the seed; 0 is uniform).
The plain random init would not do: its residual stream carries much
the same vector at every position after the first layer, so its router
sends most tokens to the same few experts. Two
fixed components ride in every token's embedding, in directions that
no other weight reads or writes: a shared direction ``u`` of norm
``a``, and a token-specific unit vector of norm ``b`` in an
``E``-dimensional subspace ``S``. Layer l's router reads only those:
column e is ``m[l, e]`` along ``u`` plus a rotation ``Q_l`` of ``S``, so
its logits are a positive scale times ``m[l] + sqrt(E) * Q_l^T t``,
where ``t`` is the token's unit vector. ``m`` is calibrated so that the
top-k of that sum picks expert e with the Zipf share (m = 0 for
``zipf_s == 0``). Routing then depends on the token id alone.

Greedy stream. Plain random weights decode greedily into a short
cycle of tokens whose length, and so the experts it keeps using, the
seed decides. So the head adds to each token's column ``SUCCESSOR_GAIN``
times the random part of its predecessor's embedding, the predecessors
taken along one seeded cycle through the whole vocabulary: the token
after x is then succ(x) by a wide margin at the published widths, a
request's answer is a run of distinct tokens, and its routing is the
Zipf law's, token by token, on every seed.

The reference imports nothing of the program. It recomputes the same
weights from the seed, bit for bit (made by the same jitted calls in
the same matmul precision), then runs one layer at a time and one
expert at a time at "highest" matmul precision. Where the served routing choice
at a position trails the reference router's top-k by no more than
``tie`` (a near tie that bf16 rounding can flip), the reference follows
it; anywhere else it routes by its own top-k.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# Norms of the two routing components in the embedding, as a share of
# sqrt(d), and the nominal scale sqrt(d)/||h|| the router's columns are
# sized for (only the gate temperature depends on it, not the top-k).
SKEW_NORM = 0.125
ROUTER_GAIN = 4.0
# The head's weight on the predecessor's embedding, beside its random
# columns of the same scale (see "Greedy stream" above).
SUCCESSOR_GAIN = 2.0
# Largest finite float8_e4m3fn value: the control's per-channel scale.
FP8_MAX = 448.0
PAD = 256  # reference sequences are padded to a multiple of this


class Dims(NamedTuple):
    d: int
    H: int
    KV: int
    hd: int
    F: int
    E: int
    k: int
    V: int
    L: int
    theta: float
    eps: float


def dims(c: dict) -> Dims:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return Dims(d, H, c["num_key_value_heads"], c.get("head_dim") or d // H,
                c["intermediate_size"], c["num_local_experts"],
                c["num_experts_per_tok"], c["vocab_size"],
                c["num_hidden_layers"], float(c["rope_theta"]),
                float(c["rms_norm_eps"]))


def token_flops(c: dict, context: float) -> float:
    """Model FLOPs of one token at ``context`` earlier positions."""
    import flops
    m = dims(c)
    return flops.gqa_moe_token_flops(m.d, m.H, m.KV, m.hd, m.F, m.E, m.k,
                                     m.V, m.L, context)


# ---------------------------------------------------------------- skew
def zipf_calibration(E: int, k: int, s: float, n: int = 16384,
                     iters: int = 300) -> np.ndarray:
    """Offsets m [E], most popular first, such that the top-k of
    ``m + sqrt(E) * t`` (t uniform on the unit sphere) picks expert r
    with share ~ (r+1)^-s / H. Fixed Monte Carlo draws: the same m for
    every seed."""
    p = np.arange(1, E + 1, dtype=np.float64) ** -s
    p /= p.sum()
    if p[0] > 1.0 / k:
        raise ValueError(f"Zipf s={s} over {E} experts asks a share "
                         f"{p[0]:.3f} > 1/k of one expert")
    rng = np.random.default_rng(0)
    z = rng.standard_normal((n, E))
    z *= math.sqrt(E) / np.linalg.norm(z, axis=1, keepdims=True)
    m = np.log(p) - np.log(p).mean()
    for _ in range(iters):
        top = np.argpartition(-(m + z), k - 1, axis=1)[:, :k]
        share = np.bincount(top.ravel(), minlength=E) / (n * k)
        m += 0.5 * (np.log(p) - np.log(np.maximum(share, 1e-6)))
    return m


def zipf_offsets(c: dict, seed: int, zipf_s: float) -> np.ndarray:
    """m [L, E]: the calibrated offsets, expert ranks shuffled per layer
    by the seed (all 0 for zipf_s == 0: uniform)."""
    m = dims(c)
    if zipf_s == 0:
        return np.zeros((m.L, m.E), np.float32)
    base = zipf_calibration(m.E, m.k, zipf_s)
    rng = np.random.default_rng([seed, 3])
    out = np.zeros((m.L, m.E), np.float32)
    for l in range(m.L):
        out[l, rng.permutation(m.E)] = base
    return out


# ------------------------------------------------------------- weights
def _keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 4)  # top, layers, experts, skew


def _tn(key, shape, fan_in, scale=1.0):
    """Truncated-normal fan-in init, fp32."""
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
            * (scale / math.sqrt(fan_in)))


@functools.partial(jax.jit, static_argnums=0)
def _basis(m: Dims, kb):
    """[d, 1 + E] orthonormal: u, then the token subspace S."""
    g = jax.random.normal(jax.random.fold_in(kb, 0), (m.d, 1 + m.E), F32)
    return jnp.linalg.qr(g)[0]


def _proj_in(w, W):
    """w [d, ...] with the span of W's columns removed from its input."""
    w2 = w.reshape(w.shape[0], -1)
    return (w2 - W @ (W.T @ w2)).reshape(w.shape)


def _proj_out(w, W):
    """w [..., d] with the span of W's columns removed from its output."""
    w2 = w.reshape(-1, w.shape[-1])
    return (w2 - (w2 @ W) @ W.T).reshape(w.shape)


@functools.partial(jax.jit, static_argnums=0)
def _make_params(m: Dims, keys, W, offsets):
    kt, kl, _, kb = keys
    k_emb, k_out = jax.random.split(kt)
    res = 1.0 / math.sqrt(2 * m.L)
    amp = SKEW_NORM * math.sqrt(m.d)
    embed = jax.random.normal(k_emb, (m.V, m.d), F32) * 0.02
    unembed = jax.random.normal(k_out, (m.d, m.V), F32) * 0.02
    layers = []
    for l in range(m.L):
        kq, kk, kv, ko = jax.random.split(jax.random.fold_in(kl, l), 4)
        a = {"wq": _tn(kq, (m.d, m.H, m.hd), m.d),
             "wk": _tn(kk, (m.d, m.KV, m.hd), m.d),
             "wv": _tn(kv, (m.d, m.KV, m.hd), m.d),
             "wo": _tn(ko, (m.H, m.hd, m.d), m.H * m.hd, res)}
        for name in ("wq", "wk", "wv"):
            a[name] = _proj_in(a[name], W)
        a["wo"] = _proj_out(a["wo"], W)
        q = jnp.linalg.qr(jax.random.normal(
            jax.random.fold_in(kb, 2 + l), (m.E, m.E), F32))[0]
        router = (W[:, :1] * offsets[l][None, :] / (amp * ROUTER_GAIN)
                  + W[:, 1:] @ q * (math.sqrt(m.E) / (amp * ROUTER_GAIN)))
        layers.append({"a": a, "router": router})
    embed = _proj_out(embed, W)
    # the successor head: the token after x is succ(x), one cycle
    # through the whole vocabulary in a seeded order
    order = jax.random.permutation(jax.random.fold_in(kt, 2), m.V)
    pred = jnp.zeros((m.V,), jnp.int32).at[order].set(jnp.roll(order, 1))
    unembed = _proj_in(unembed, W) + SUCCESSOR_GAIN * embed[pred].T
    t = jax.random.normal(jax.random.fold_in(kb, 1), (m.V, m.E), F32)
    t = t / jnp.linalg.norm(t, axis=1, keepdims=True)
    embed = embed + amp * W[:, 0][None, :] + amp * t @ W[:, 1:].T
    bf = jnp.bfloat16
    ones = jnp.ones((m.L, m.d), bf)
    return {
        "embed": embed.astype(bf),
        "final_norm": jnp.ones((m.d,), bf),
        "unembed": unembed.astype(bf),
        "layers": {
            "ln1": ones, "ln2": ones,
            "attn": {n: jnp.stack([x["a"][n] for x in layers]).astype(bf)
                     for n in ("wq", "wk", "wv", "wo")},
            "moe": {"router": jnp.stack([x["router"] for x in layers])},
        },
    }


@functools.partial(jax.jit, static_argnums=0)
def _make_experts(m: Dims, key, W):
    k1, k3, k2 = jax.random.split(key, 3)
    w1 = _tn(k1, (m.E, m.d, m.F), m.d)
    w3 = _tn(k3, (m.E, m.d, m.F), m.d)
    w2 = _tn(k2, (m.E, m.F, m.d), m.F, 1.0 / math.sqrt(2 * m.L))
    w1 = w1 - jnp.einsum("dj,ejf->edf", W, jnp.einsum("dj,edf->ejf", W, w1))
    w3 = w3 - jnp.einsum("dj,ejf->edf", W, jnp.einsum("dj,edf->ejf", W, w3))
    w2 = w2 - jnp.einsum("efj,dj->efd", jnp.einsum("efd,dj->efj", w2, W), W)
    bf = jnp.bfloat16
    return {"w1": w1.astype(bf), "w3": w3.astype(bf), "w2": w2.astype(bf)}


class Weights:
    """The cell's weights from the seed, made on the device in the
    type they are served in (bf16; the router fp32)."""

    def __init__(self, c: dict, seed: int, zipf_s: float):
        self.dims = dims(c)
        self.keys = _keys(seed)
        self.W = _basis(self.dims, self.keys[3])
        self.offsets = jnp.asarray(zipf_offsets(c, seed, zipf_s))

    def params(self):
        """The non-expert tree, one jitted call."""
        return _make_params(self.dims, self.keys, self.W, self.offsets)

    def experts(self, layer: int):
        """Layer ``layer``'s experts stacked on the device:
        w1, w3 [E, d, F] and w2 [E, F, d]."""
        key = jax.random.fold_in(self.keys[2], layer)
        return _make_experts(self.dims, key, self.W)


# ----------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _q8(w, axes):
    """w rounded through float8_e4m3fn with one scale per output
    channel (the max over the input ``axes`` maps to FP8_MAX)."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _a8(x, fp8: bool):
    """A matmul's activation input: rounded through float8_e4m3fn with
    one scale per row for the control, as is otherwise."""
    return _q8(x, -1) if fp8 else x


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(m: Dims, fp8: bool, layers, l):
    p = jax.tree.map(lambda x: x[l].astype(F32), layers)
    if fp8:
        a = p["attn"]
        p["attn"] = {"wq": _q8(a["wq"], 0), "wk": _q8(a["wk"], 0),
                     "wv": _q8(a["wv"], 0), "wo": _q8(a["wo"], (0, 1))}
        p["moe"] = {"router": _q8(p["moe"]["router"], 0)}
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
def _attend(m: Dims, fp8: bool, p, h, served, tie, follow):
    """One layer's attention over h [S, d], then its router. ``served``
    [S, E] marks the served choice. Returns h, the MoE input x, the gate
    of each expert [S, E] (0 where not chosen), how many positions
    followed a served choice that differs from the reference top-k, and
    the largest margin by which a followed choice trailed."""
    S = h.shape[0]
    pos = jnp.arange(S, dtype=F32)
    a = p["attn"]
    x = _a8(_rms(h, p["ln1"], m.eps), fp8)
    q = _rope(jnp.einsum("sd,dhk->shk", x, a["wq"]), pos, m.theta)
    k = _rope(jnp.einsum("sd,dhk->shk", x, a["wk"]), pos, m.theta)
    v = jnp.einsum("sd,dhk->shk", x, a["wv"])
    g = m.H // m.KV
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("shk,thk->hst", q, k) / math.sqrt(m.hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v)
    h = h + jnp.einsum("shk,hkd->sd", _a8(o.reshape(S, -1), fp8).reshape(
        o.shape), a["wo"])
    x = _a8(_rms(h, p["ln2"], m.eps), fp8)
    logits = x @ p["moe"]["router"]                          # [S, E]
    kth = jnp.sort(logits, -1)[:, -m.k][:, None]
    top = jax.nn.one_hot(jax.lax.top_k(logits, m.k)[1], m.E).sum(1) > 0
    valid = served.sum(-1) == m.k
    trail = kth[:, 0] - jnp.min(jnp.where(served, logits, jnp.inf), -1)
    use = follow & valid & (trail <= tie)
    chosen = jnp.where(use[:, None], served, top)
    gates = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1)
    flipped = use & jnp.any(served != top, -1)
    return (h, x, gates, flipped.sum(),
            jnp.max(jnp.where(flipped, trail, 0.0)))


@functools.partial(jax.jit, static_argnums=0)
def _expert(fp8: bool, h, x, w1, w3, w2, gate):
    w1, w3, w2 = (w.astype(F32) for w in (w1, w3, w2))
    if fp8:
        w1, w3, w2 = _q8(w1, 0), _q8(w3, 0), _q8(w2, 0)
    y = _a8(jax.nn.silu(x @ w1) * (x @ w3), fp8) @ w2
    return h + gate[:, None] * y


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(m: Dims, fp8: bool, top, h):
    return _a8(_rms(h, top["final_norm"], m.eps), fp8) @ top["unembed"]


def reference_logits(weights: Weights, seqs: Sequence[Sequence[int]],
                     served: Optional[Sequence[np.ndarray]], tie: float,
                     *, fp8: bool = False):
    """Logits [S_i, V] (host, fp32) of each token sequence fed from
    position 0, and the routing-follow counts.

    ``served[i]`` [S_i, L, k] holds the served experts of sequence i at
    each position and layer (follow them within ``tie``); None, or
    ``fp8=True`` for the control, routes by the reference's own top-k.
    With ``fp8`` every matmul takes its inputs rounded through
    float8_e4m3fn: weights per output channel, activations per row."""
    m = weights.dims
    follow = served is not None and not fp8
    lens = [len(s) for s in seqs]
    # The weights are made as the program's were, outside the "highest"
    # context: under it the jitted makers would compile anew, and on a
    # TPU their matmuls would round otherwise than the served weights.
    highest = functools.partial(jax.default_matmul_precision, "highest")
    params = weights.params()
    top = _top_weights(m, fp8, params)
    hs, masks = [], []
    for i, seq in enumerate(seqs):
        S = -(-lens[i] // PAD) * PAD
        toks = np.zeros(S, np.int32)
        toks[:lens[i]] = seq
        hs.append(top["embed"][jnp.asarray(toks)])
        mask = np.zeros((m.L, S, m.E), bool)
        if follow:
            r = np.asarray(served[i])
            # every served row here lists k experts: a -1 would mark
            # expert E - 1
            assert (r >= 0).all(), "a served route lists fewer than k"
            for l in range(m.L):
                np.put_along_axis(mask[l, :lens[i]], r[:, l], True, -1)
        masks.append(mask)
    flips, margin = 0, 0.0
    for l in range(m.L):
        p = _layer_weights(m, fp8, params["layers"], l)
        ex = weights.experts(l)
        with highest():
            gates = []
            for i in range(len(seqs)):
                h, x, g, f, mg = _attend(m, fp8, p, hs[i],
                                         jnp.asarray(masks[i][l]), tie, follow)
                hs[i] = h
                gates.append((x, g))
                flips += int(f)
                margin = max(margin, float(mg))
            for e in range(m.E):
                for i, (x, g) in enumerate(gates):
                    # waiting for each keeps one expert's fp32 weights on
                    # the device, not a layer's
                    hs[i] = jax.block_until_ready(_expert(
                        fp8, hs[i], x, ex["w1"][e], ex["w3"][e], ex["w2"][e],
                        g[:, e]))
        del ex, gates
    del params
    with highest():
        out = [np.asarray(_head(m, fp8, top, h))[:n]
               for h, n in zip(hs, lens)]
    return out, {"followed_flips": flips, "followed_max_margin": margin}

