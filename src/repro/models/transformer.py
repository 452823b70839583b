"""Model composition: init / full forward / loss / KV-cache decode for
every architecture family (dense, moe, ssm, hybrid, encdec, vlm).

Layers are stacked into homogeneous groups and iterated with
``lax.scan`` so the lowered HLO stays small for 60-72 layer configs.

  dense/moe/ssm : one stack [L]
  hybrid        : periods of ``attn_every``: attn stack [P] + ssm stack [P, per]
  vlm           : periods of ``cross_attn_every``: plain [P, per] + cross [P]
  encdec        : encoder stack [Le] + decoder-with-cross stack [Ld]
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (chunked_softmax_xent, embed_init, init_gelu_mlp,
                                 gelu_mlp, init_swiglu, rms_norm,
                                 sinusoidal_positions, swiglu)
from repro.models.sharding import constrain

AUX_WEIGHT = 0.01


# =====================================================================
# init
# =====================================================================
def _param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _init_ffn(key, cfg, dtype, use_moe: bool):
    if cfg.family == "encdec":
        return "mlp", init_gelu_mlp(key, cfg.d_model, cfg.d_ff, cfg.num_layers, dtype)
    if use_moe:
        return "moe", moe_lib.init_moe(key, cfg, dtype)
    return "mlp", init_swiglu(key, cfg.d_model, cfg.d_ff, cfg.num_layers, dtype)


def _init_block(key, cfg, *, kind: str, cross: bool, causal: bool, dtype,
                use_moe: bool = None):
    if use_moe is None:
        use_moe = cfg.is_moe
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if kind == "attn":
        p["attn"] = attn.init_attention(ks[0], cfg, dtype)
    else:
        p["ssm"] = ssm_lib.init_ssm(ks[0], cfg, dtype)
    if cross:
        p["ln_c"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = attn.init_cross_attention(ks[1], cfg, dtype)
    if cfg.family != "ssm":
        name, ffn = _init_ffn(ks[2], cfg, dtype, use_moe)
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p[name] = ffn
    return p


def _stack_init(key, n, fn):
    keys = jax.random.split(key, max(n, 1))[:n]
    return jax.vmap(fn)(keys)


def init_params(cfg, key, dtype=None):
    dtype = dtype or _param_dtype(cfg)
    k_emb, k_layers, k_enc, k_out = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(k_out, (cfg.d_model, cfg.vocab_size), dtype)

    fam = cfg.family
    if fam != "hybrid" and cfg.is_moe:
        assert cfg.moe_every == 1, "moe_every>1 only supported for hybrid"
    if fam in ("dense", "moe"):
        params["layers"] = _stack_init(
            k_layers, cfg.num_layers,
            lambda k: _init_block(k, cfg, kind="attn", cross=False, causal=True,
                                  dtype=dtype))
    elif fam == "ssm":
        params["layers"] = _stack_init(
            k_layers, cfg.num_layers,
            lambda k: _init_block(k, cfg, kind="ssm", cross=False, causal=True,
                                  dtype=dtype))
    elif fam == "hybrid":
        P = cfg.num_layers // cfg.attn_every
        per = cfg.attn_every - 1
        # the FFN rhythm (dense vs MoE) must repeat with the period
        assert cfg.attn_every % max(cfg.moe_every, 1) == 0
        ka, ks_ = jax.random.split(k_layers)
        params["attn_layers"] = _stack_init(
            ka, P, lambda k: _init_block(k, cfg, kind="attn", cross=False,
                                         causal=True, dtype=dtype,
                                         use_moe=cfg.has_moe(0)))
        inner_keys = jax.random.split(ks_, per)
        params["ssm_layers"] = tuple(
            _stack_init(inner_keys[j], P,
                        lambda k, j=j: _init_block(
                            k, cfg, kind="ssm", cross=False, causal=True,
                            dtype=dtype, use_moe=cfg.has_moe(j + 1)))
            for j in range(per))
    elif fam == "vlm":
        P = cfg.num_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        kp, kc = jax.random.split(k_layers)
        params["layers"] = _stack_init(
            kp, P, lambda kk: _stack_init(
                kk, per, lambda k: _init_block(k, cfg, kind="attn", cross=False,
                                               causal=True, dtype=dtype)))
        params["cross_layers"] = _stack_init(
            kc, P, lambda k: _init_block(k, cfg, kind="attn", cross=True,
                                         causal=True, dtype=dtype))
    elif fam == "encdec":
        params["enc_layers"] = _stack_init(
            k_enc, cfg.encoder_layers,
            lambda k: _init_block(k, cfg, kind="attn", cross=False, causal=False,
                                  dtype=dtype))
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["layers"] = _stack_init(
            k_layers, cfg.num_layers,
            lambda k: _init_block(k, cfg, kind="attn", cross=True, causal=True,
                                  dtype=dtype))
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def unembed_matrix(params):
    return params["unembed"] if "unembed" in params else params["embed"].T


# =====================================================================
# full-sequence blocks
# =====================================================================
def _attn_full(p, cfg, h, positions, window):
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        return h + attn.mla_full(p["attn"], cfg, x, positions, window=window)
    return h + attn.gqa_full(p["attn"], cfg, x, positions, window=window)


def _enc_attn_full(p, cfg, h, positions):
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    return h + attn.gqa_full(p["attn"], cfg, x, positions, window=None,
                             causal=False)


def _cross_full(p, cfg, h, enc):
    x = rms_norm(h, p["ln_c"], cfg.norm_eps)
    kv = attn.cross_kv(p["cross"], enc)
    return h + attn.cross_attend(p["cross"], cfg, x, kv)


def _ffn_full(p, cfg, h, moe_path):
    if cfg.family == "ssm":
        return h, 0.0
    x = rms_norm(h, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, aux = moe_lib.moe_apply(p["moe"], cfg, x, path=moe_path)
        return h + y, aux
    if cfg.family == "encdec":
        return h + gelu_mlp(p["mlp"], x), 0.0
    return h + swiglu(p["mlp"], x), 0.0


def _block_full(p, cfg, h, positions, *, kind, window, enc, moe_path):
    h = constrain(h, "batch", None, None)
    if kind == "attn":
        h = _attn_full(p, cfg, h, positions, window)
    else:
        h = h + ssm_lib.ssd_full(p["ssm"], cfg, rms_norm(h, p["ln1"], cfg.norm_eps))
    if "cross" in p:
        h = _cross_full(p, cfg, h, enc)
    h, aux = _ffn_full(p, cfg, h, moe_path)
    return h, aux


# =====================================================================
# full forward (train / prefill)
# =====================================================================
def encoder_forward(params, cfg, frames):
    """frames [B, T, d] (stub frontend output) -> encoder states."""
    B, T, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    h = frames + sinusoidal_positions(pos, cfg.d_model).astype(frames.dtype)

    def body(h, p):
        h = _enc_attn_full(p, cfg, h, pos)
        h, _ = _ffn_full(p, cfg, h, "dense")
        return h, None

    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def forward(params, cfg, tokens, *, enc=None, window: Optional[int] = None,
            moe_path: str = "auto", remat: bool = False):
    """tokens [B,S] -> (hidden [B,S,d] pre-final-norm, aux_loss scalar)."""
    B, S = tokens.shape
    h = params["embed"][tokens]  # JAX gathers; vocab shard handled by SPMD
    h = constrain(h, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if cfg.pos_emb == "sinusoidal":
        h = h + sinusoidal_positions(positions, cfg.d_model).astype(h.dtype)

    fam = cfg.family

    def scan_blocks(h, stacked, kind, aux0):
        def body(carry, p):
            hh, aux = carry
            hh, a = _block_full(p, cfg, hh, positions, kind=kind, window=window,
                                enc=enc, moe_path=moe_path)
            return (hh, aux + a), None
        body = jax.checkpoint(body) if remat else body
        (h, aux), _ = jax.lax.scan(body, (h, aux0), stacked)
        return h, aux

    aux = jnp.zeros((), jnp.float32)
    if fam in ("dense", "moe", "ssm", "encdec"):
        kind = "ssm" if fam == "ssm" else "attn"
        h, aux = scan_blocks(h, params["layers"], kind, aux)
    elif fam == "hybrid":
        def period(carry, ps):
            hh, aux = carry
            pa, pss = ps
            hh, a = _block_full(pa, cfg, hh, positions, kind="attn",
                                window=window, enc=enc, moe_path=moe_path)
            aux = aux + a
            for p_j in pss:  # per-position stacks differ (dense/MoE rhythm)
                hh, a2 = _block_full(p_j, cfg, hh, positions, kind="ssm",
                                     window=window, enc=enc, moe_path=moe_path)
                aux = aux + a2
            return (hh, aux), None
        period = jax.checkpoint(period) if remat else period
        (h, aux), _ = jax.lax.scan(period, (h, aux),
                                   (params["attn_layers"], params["ssm_layers"]))
    elif fam == "vlm":
        def period(carry, ps):
            hh, aux = carry
            p_plain, p_cross = ps

            def inner(c, p):
                hh2, aux2 = c
                hh2, a2 = _block_full(p, cfg, hh2, positions, kind="attn",
                                      window=window, enc=enc, moe_path=moe_path)
                return (hh2, aux2 + a2), None
            (hh, aux), _ = jax.lax.scan(inner, (hh, aux), p_plain)
            hh, a = _block_full(p_cross, cfg, hh, positions, kind="attn",
                                window=window, enc=enc, moe_path=moe_path)
            return (hh, aux + a), None
        period = jax.checkpoint(period) if remat else period
        (h, aux), _ = jax.lax.scan(period, (h, aux),
                                   (params["layers"], params["cross_layers"]))
    else:
        raise ValueError(fam)
    return h, aux


def logits_from_hidden(params, cfg, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ unembed_matrix(params)).astype(jnp.float32)
    return constrain(logits, "batch", None, "vocab")


def loss_fn(params, cfg, batch, *, moe_path: str = "auto", remat: bool = True):
    enc = None
    if cfg.family == "encdec":
        enc = encoder_forward(params, cfg, batch["frames"])
    elif cfg.family == "vlm":
        enc = batch["patches"]
    h, aux = forward(params, cfg, batch["tokens"], enc=enc, moe_path=moe_path,
                     remat=remat)
    xent = chunked_softmax_xent(h, unembed_matrix(params), batch["labels"],
                                norm_w=params["final_norm"], eps=cfg.norm_eps)
    return xent + AUX_WEIGHT * aux


def prefill(params, cfg, tokens, *, enc=None, moe_path: str = "auto"):
    """Full forward returning last-position logits (no [B,S,V] blowup)."""
    h, _ = forward(params, cfg, tokens, enc=enc, moe_path=moe_path)
    return logits_from_hidden(params, cfg, h[:, -1:, :])[:, 0]


# =====================================================================
# decode state
# =====================================================================
def _attn_cache_init(cfg, batch, cache_len, dtype):
    if cfg.use_mla:
        return attn.mla_cache_init(cfg, batch, cache_len, dtype)
    return attn.gqa_cache_init(cfg, batch, cache_len, dtype)


def init_decode_state(params, cfg, batch: int, cache_len: int, *,
                      dtype=None, enc=None):
    """Build the per-layer decode cache pytree (stacked like params)."""
    dtype = dtype or _param_dtype(cfg)
    fam = cfg.family

    def stack(n, fn):
        one = fn()
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), one)

    state: Dict[str, Any] = {}
    if fam in ("dense", "moe", "encdec"):
        state["layers"] = stack(cfg.num_layers,
                                lambda: _attn_cache_init(cfg, batch, cache_len, dtype))
    elif fam == "ssm":
        state["layers"] = stack(cfg.num_layers,
                                lambda: ssm_lib.ssm_state_init(cfg, batch, dtype))
    elif fam == "hybrid":
        P = cfg.num_layers // cfg.attn_every
        per = cfg.attn_every - 1
        state["attn_layers"] = stack(P, lambda: _attn_cache_init(cfg, batch,
                                                                 cache_len, dtype))
        state["ssm_layers"] = tuple(
            stack(P, lambda: ssm_lib.ssm_state_init(cfg, batch, dtype))
            for _ in range(per))
    elif fam == "vlm":
        P = cfg.num_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        state["layers"] = stack(
            P, lambda: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (per, *x.shape)),
                _attn_cache_init(cfg, batch, cache_len, dtype)))
        state["cross_layers"] = stack(P, lambda: _attn_cache_init(cfg, batch,
                                                                  cache_len, dtype))
    # precomputed cross K/V over frontend states
    if fam == "encdec":
        assert enc is not None, "encdec decode needs encoder states"
        state["cross_kv"] = jax.vmap(
            lambda p: attn.cross_kv(p["cross"], enc))(params["layers"])
    elif fam == "vlm":
        assert enc is not None, "vlm decode needs patch embeddings"
        state["cross_kv"] = jax.vmap(
            lambda p: attn.cross_kv(p["cross"], enc))(params["cross_layers"])
    return state


# =====================================================================
# decode step
# =====================================================================
def _attn_decode(p, cfg, h, cache, pos, window):
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        y, cache = attn.mla_decode(p["attn"], cfg, x, cache, pos, window=window)
    else:
        y, cache = attn.gqa_decode(p["attn"], cfg, x, cache, pos, window=window)
    return h + y, cache


# One layer's GQA decode block runs as three compiled programs: the
# norm, Q/K/V with rope and the new K/V's scatter (``_gqa_pre``); the
# attention core (``_gqa_attend*`` or the paged kernel, which stays a
# program of its own); and ``wo`` with the residual (``_gqa_post``).
# Eagerly the block is ~100 separate dispatches, and on a TPU each one
# leaves the chip idle while the host issues it. Nothing is donated:
# the caller's pool stays readable.
@functools.partial(jax.jit, static_argnames=("cfg",))
def _gqa_pre(ln1, pa, cfg, h, cache, pos_vec, block_tables):
    """-> (q [B,Hp,hd], cache with each row's new K/V written: at
    (row, pos) when ``block_tables`` is None, else through the table)."""
    x = rms_norm(h, ln1, cfg.norm_eps)
    q, k_new, v_new = attn.gqa_decode_qkv(pa, cfg, x, pos_vec)
    cells = (attn.dense_cells(pos_vec) if block_tables is None else
             attn.paged_cells(block_tables, pos_vec, cache["k"].shape[1]))
    return q, attn.gqa_append(cache, k_new, v_new, cells)


_gqa_attend = jax.jit(attn.gqa_attend)
_gqa_attend_paged = jax.jit(attn.gqa_attend_paged)


@jax.jit
def _gqa_post(wo, h, out):
    return h + attn.gqa_out(wo, out, h.dtype)


def _attn_decode_multipos(p, cfg, h, cache, pos_vec):
    """Per-row-position decode (continuous batching): pos_vec [B]."""
    if cfg.use_mla:
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        y, cache = attn.mla_decode_multipos(p["attn"], cfg, x, cache, pos_vec)
        return h + y, cache
    q, cache = _gqa_pre(p["ln1"], p["attn"], cfg, h, cache, pos_vec, None)
    out = _gqa_attend(q, cache["k"], cache["v"], pos_vec)
    return _gqa_post(p["attn"]["wo"], h, out), cache


def _attn_decode_paged(p, cfg, h, cache, pos_vec, block_tables):
    """Per-row-position decode over a paged KV pool: ``cache`` is one
    layer's block pool and ``block_tables [B, T]`` maps each row's
    logical blocks to physical ones (see ``repro.core.paged_kv``).
    Rows may share a table at distinct positions (chunked prefill's
    virtual rows) — see the multi-position append contract on
    ``repro.models.attention.gqa_decode_paged``."""
    if cfg.use_mla:
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        y, cache = attn.mla_decode_paged(p["attn"], cfg, x, cache, pos_vec,
                                         block_tables)
        return h + y, cache
    q, cache = _gqa_pre(p["ln1"], p["attn"], cfg, h, cache, pos_vec,
                        block_tables)
    if attn.PAGED_ATTN_IMPL == "xla":
        out = _gqa_attend_paged(q, cache, block_tables, pos_vec)
    else:
        from repro.kernels import ops as kops
        out = kops.paged_attention(q, cache["k"], cache["v"], block_tables,
                                   pos_vec, impl=attn.PAGED_ATTN_IMPL)
    return _gqa_post(p["attn"]["wo"], h, out), cache


def _block_decode(p, cfg, h, cache, pos, *, kind, window, cross_kv, moe_path):
    if kind == "attn":
        h, cache = _attn_decode(p, cfg, h, cache, pos, window)
    else:
        y, cache = ssm_lib.ssd_decode(p["ssm"], cfg,
                                      rms_norm(h, p["ln1"], cfg.norm_eps), cache)
        h = h + y
    if "cross" in p and cross_kv is not None:
        x = rms_norm(h, p["ln_c"], cfg.norm_eps)
        h = h + attn.cross_attend(p["cross"], cfg, x, cross_kv)
    h, _ = _ffn_full(p, cfg, h, moe_path)
    return h, cache


def decode_step(params, cfg, state, token, pos, *, window: Optional[int] = None,
                moe_path: str = "auto"):
    """token [B,1] int32, pos scalar int32 -> (logits [B,V], new state)."""
    B = token.shape[0]
    h = params["embed"][token]
    if cfg.pos_emb == "sinusoidal":
        p2 = jnp.broadcast_to(jnp.reshape(pos, (1, 1)), (B, 1))
        h = h + sinusoidal_positions(p2, cfg.d_model).astype(h.dtype)

    fam = cfg.family
    new_state = dict(state)

    if fam in ("dense", "moe", "ssm", "encdec"):
        kind = "ssm" if fam == "ssm" else "attn"
        cross = state.get("cross_kv")
        xs = (params["layers"], state["layers"]) if cross is None else (
            params["layers"], state["layers"], cross)

        def body(h, xs_):
            if cross is None:
                p, c = xs_
                ckv = None
            else:
                p, c, ckv = xs_
            h, c = _block_decode(p, cfg, h, c, pos, kind=kind, window=window,
                                 cross_kv=ckv, moe_path=moe_path)
            return h, c
        h, new_caches = jax.lax.scan(body, h, xs)
        new_state["layers"] = new_caches
    elif fam == "hybrid":
        def body(h, xs_):
            pa, ca, pss, css = xs_
            h, ca = _block_decode(pa, cfg, h, ca, pos, kind="attn", window=window,
                                  cross_kv=None, moe_path=moe_path)
            new_css = []
            for p_j, c_j in zip(pss, css):
                h, c_j = _block_decode(p_j, cfg, h, c_j, pos, kind="ssm",
                                       window=window, cross_kv=None,
                                       moe_path=moe_path)
                new_css.append(c_j)
            return h, (ca, tuple(new_css))
        h, (new_a, new_s) = jax.lax.scan(
            body, h, (params["attn_layers"], state["attn_layers"],
                      params["ssm_layers"], state["ssm_layers"]))
        new_state["attn_layers"] = new_a
        new_state["ssm_layers"] = new_s
    elif fam == "vlm":
        def body(h, xs_):
            p_plain, c_plain, p_cross, c_cross, ckv = xs_

            def inner(h2, xs2):
                p, c = xs2
                h2, c = _block_decode(p, cfg, h2, c, pos, kind="attn",
                                      window=window, cross_kv=None,
                                      moe_path=moe_path)
                return h2, c
            h, c_plain = jax.lax.scan(inner, h, (p_plain, c_plain))
            h, c_cross = _block_decode(p_cross, cfg, h, c_cross, pos, kind="attn",
                                       window=window, cross_kv=ckv,
                                       moe_path=moe_path)
            return h, (c_plain, c_cross)
        h, (new_p, new_c) = jax.lax.scan(
            body, h, (params["layers"], state["layers"], params["cross_layers"],
                      state["cross_layers"], state["cross_kv"]))
        new_state["layers"] = new_p
        new_state["cross_layers"] = new_c
    else:
        raise ValueError(fam)

    logits = logits_from_hidden(params, cfg, h)[:, 0]
    return logits, new_state
