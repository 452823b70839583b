"""Smoke run of the offload serving path on one TPU chip.

Serves Mixtral-8x7B at its published widths (d_model 4096, 32 q / 8 kv
heads of 128, 8 experts of d_ff 14336 with top-2 routing, vocabulary
32000, bf16), cut to 4 layers, with random weights made from a seed:

  build      the non-expert weights on the device; every expert drawn
             alone and moved straight into the host ExpertStore.
  serve/xla  ContinuousOffloadServer (paged KV, LFU, 4 expert slots per
             layer) runs a few requests together to completion on the
             XLA kernels. Every request must complete with its token
             count and every logit must be finite.
  reference  a float32 forward of the same model over request 1's
             tokens, one layer and one expert at a time (no more than
             one expert's weights on the device), at "highest" matmul
             precision. The
             served logits must match it within REF_TOL, and each
             served routing choice must be the reference router's or
             within ROUTE_TIE of it.
  serve/pallas  the same requests on the Pallas grouped-FFN and paged
             attention kernels. Greedy tokens must equal serve/xla's;
             a request whose tokens part from them must match the
             reference over its own tokens, as above.

It needs a TPU: with none, or when any check fails, it exits non-zero
and prints no result line. The times it prints are those of this one
run, compilation included — a smoke timing, not a benchmark. The last
line of its output is {"ok": true, "device": {...}}.

    python chip_smoke.py
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import resource
import sys
import time
from typing import Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import init_offloaded_params  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.layers import rms_norm  # noqa: E402
from repro.serving import ContinuousOffloadServer  # noqa: E402

SEED = 0
PROMPT_LENS = (8, 12, 16)
MAX_NEW = 8
CACHE_SLOTS = 4
KV_BLOCK = 16
# Largest relative L2 error ||served - ref|| / ||ref|| of the logits
# allowed at any position. The served path keeps bf16 weights and
# activations (unit roundoff 2^-9 per rounding), the reference float32.
REF_TOL = 0.05
# Largest amount by which a served routing choice may trail the
# reference router's top-k, in router logits (which are ~N(0, 1) here):
# bf16 rounding moves them by about 1e-2.
ROUTE_TIE = 0.1


def smoke_config():
    """Mixtral-8x7B at full width, cut to 4 layers (every layer is MoE,
    so 4 layers are four whole periods)."""
    return dataclasses.replace(get_config("mixtral-8x7b"), num_layers=4)


def memory_line() -> str:
    """Device bytes in use and their peak so far (where the backend
    reports them), and the host's peak RSS."""
    mem = jax.devices()[0].memory_stats() or {}
    return (f"device_bytes_in_use={mem.get('bytes_in_use')} "
            f"peak_device_bytes={mem.get('peak_bytes_in_use')} "
            f"device_bytes_limit={mem.get('bytes_limit')} "
            f"host_peak_rss_bytes="
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")


def make_prompts(cfg, lens: Sequence[int], seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
            for n in lens]


@contextlib.contextmanager
def compile_clock():
    """Yields a dict whose ``"s"`` sums JAX's trace, lowering and
    backend-compile durations while the block runs."""
    acc = {"s": 0.0}
    events = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def listen(event, duration, **_):
        if event in events:
            acc["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def serve(params, store, cfg, prompts, max_new: int, *, ffn_impl: str,
          paged_impl: str) -> Dict:
    """Run ``prompts`` together through one ContinuousOffloadServer to
    completion. Returns per request its tokens, the logits of every step
    it was fed in ([len(tokens), V] fp32, row i after token i) and the
    experts it routed to ([len(tokens), L, k], from the server's trace)."""
    old = attn.PAGED_ATTN_IMPL
    attn.PAGED_ATTN_IMPL = paged_impl
    try:
        lens = [len(p) + max_new for p in prompts]
        srv = ContinuousOffloadServer(
            params, cfg, store=store, cache_slots=CACHE_SLOTS,
            max_batch=len(prompts), cache_len=max(lens), policy="lfu",
            ffn_impl=ffn_impl, kv_block_size=KV_BLOCK,
            kv_num_blocks=sum(-(-n // KV_BLOCK) for n in lens))
        rids = [srv.submit(p, max_new=max_new) for p in prompts]
        logits: Dict[int, list] = {rid: [] for rid in rids}
        while srv.pending:
            live = [rid for rid in rids if rid not in srv.finished]
            srv.step()
            # FIFO admission into an idle server puts request i in slot i
            if srv.step_count == 1 and \
                    [r.rid for r in srv.slots[:len(rids)]] != rids:
                raise AssertionError("requests were not admitted together")
            rows = np.asarray(srv.last_logits)
            for i, rid in enumerate(rids):
                if rid in live:
                    logits[rid].append(rows[i])
        if srv.kv_preemptions:
            raise AssertionError("a request was preempted")
        routes = []
        for rid in rids:
            r = np.zeros((len(srv.result(rid)), cfg.num_layers,
                          cfg.num_experts_per_tok), np.int64)
            for tok, layer, acts, _ in srv.trace.request_steps(rid):
                r[tok, layer] = acts
            routes.append(r)
        out = {"tokens": [srv.result(rid) for rid in rids],
               "logits": [np.stack(logits[rid]) for rid in rids],
               "routes": routes, "stats": srv.stats(),
               "largest_live_array": max(a.nbytes for a in jax.live_arrays())}
        del srv
        gc.collect()
        return out
    finally:
        attn.PAGED_ATTN_IMPL = old


def reference_logits(params, store, cfg, tokens: Sequence[int],
                     routes: np.ndarray):
    """Float32 forward of the model over ``tokens``: ``(logits [S, V],
    router logits [L, S, E])``.

    Built from the same weights (bf16 ones upcast) one layer at a time,
    at "highest" matmul precision: causal GQA attention over the whole
    sequence, then every expert of the layer on every position, one
    expert at a time, mixed by softmax gates over the router logits of
    the experts the served path chose (``routes`` [S, L, k]). Following
    the served choice keeps one near-tied router decision, which bf16
    rounding may flip, from standing in for an error; ``check_routes``
    holds the choices themselves to the reference's router."""
    S = len(tokens)
    positions = jnp.arange(S)[None, :]

    @jax.jit
    def attend(p, h, chosen):
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        h = h + attn.gqa_full(p["attn"], cfg, x, positions)
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        logits = x @ p["moe"]["router"]                        # [1,S,E]
        gates = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
        return h, x, gates, logits[0]

    @jax.jit
    def add_expert(h, x, w, gate):                        # gate [1,S]
        y = (jax.nn.silu(x @ w["w1"]) * (x @ w["w3"])) @ w["w2"]
        return h + gate[..., None] * y

    router = []
    with jax.default_matmul_precision("highest"):
        top = _f32({k: v for k, v in params.items() if k != "layers"})
        h = top["embed"][jnp.asarray(tokens, jnp.int32)][None]
        for l in range(cfg.num_layers):
            p = _f32(jax.tree.map(lambda x: x[l], params["layers"]))
            chosen = np.zeros((1, S, cfg.num_experts), bool)
            np.put_along_axis(chosen[0], routes[:, l], True, axis=-1)
            h, x, gates, r = attend(p, h, jnp.asarray(chosen))
            router.append(np.asarray(r))
            for e in range(cfg.num_experts):
                # waiting for each expert keeps one expert's float32
                # weights (and the compiler's bf16 splits of them for
                # "highest" precision) on the device, not a layer's
                h = jax.block_until_ready(add_expert(
                    h, x, _f32(store.fetch((l, e))), gates[..., e]))
        logits = jax.jit(lambda t, hh: tf.logits_from_hidden(t, cfg, hh))(
            top, h)
        return np.asarray(logits[0]), np.stack(router)


def check_routes(routes: np.ndarray, router: np.ndarray) -> int:
    """Each served routing choice (``routes`` [S, L, k]) is the
    reference router's top-k, or is within ROUTE_TIE of it: every chosen
    expert's reference logit is at least the k-th largest less
    ROUTE_TIE. Returns how many (position, layer) choices differ."""
    k = routes.shape[-1]
    differ = 0
    for s_, l in np.ndindex(routes.shape[:2]):
        r = router[l, s_]
        kth = np.sort(r)[-k]
        if set(routes[s_, l]) == set(np.argsort(-r)[:k]):
            continue
        differ += 1
        if r[routes[s_, l]].min() < kth - ROUTE_TIE:
            raise AssertionError(
                f"position {s_} layer {l} routed to {routes[s_, l]} whose "
                f"reference router logits {r[routes[s_, l]]} trail the "
                f"top-{k} ({kth:.4g}) by more than {ROUTE_TIE}")
    return differ


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x).astype(jnp.float32), tree)


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-position relative L2 error of logits [S, V]."""
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))


def check_served(out: Dict, prompts, max_new: int) -> None:
    for toks, lg, p in zip(out["tokens"], out["logits"], prompts):
        if len(toks) != len(p) + max_new or toks[:len(p)] != list(p):
            raise AssertionError(f"request of {len(p)} prompt tokens came "
                                 f"back with {len(toks)} tokens")
        if lg.shape[0] != len(toks):
            raise AssertionError(f"{lg.shape[0]} logit rows for "
                                 f"{len(toks)} tokens")
        if not np.isfinite(lg).all():
            raise AssertionError("non-finite logits")


def check_reference(params, store, cfg, out: Dict, i: int, report) -> float:
    """Request ``i`` of a serve run against the float32 reference over
    its own tokens and routing. Returns the largest relative error."""
    ref, router = reference_logits(params, store, cfg, out["tokens"][i],
                                   out["routes"][i])
    differ = check_routes(out["routes"][i], router)
    err = rel_err(out["logits"][i], ref)
    report(f"reference: request {i + 1}, {len(err)} positions, max relative "
           f"L2 error {err.max():.4g} (mean {err.mean():.4g}, tol {REF_TOL}); "
           f"{differ} of {err.size * cfg.num_layers} routing choices differ "
           f"within {ROUTE_TIE}")
    if not err.max() <= REF_TOL:
        raise AssertionError(f"served logits of request {i + 1} off the "
                             f"float32 reference by {err.max():.4g} > "
                             f"{REF_TOL}")
    return float(err.max())


def run(cfg, *, pallas_impl: str, seed: int = SEED,
        prompt_lens: Sequence[int] = PROMPT_LENS, max_new: int = MAX_NEW,
        report=print) -> Dict:
    """All phases; raises on any failed check."""
    prompts = make_prompts(cfg, prompt_lens, seed)
    res: Dict = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        with compile_clock() as c:
            val = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        report(f"smoke timing (one run, not a benchmark): phase={name} "
               f"wall_s={wall:.3f} compile_s={c['s']:.3f}")
        report(f"memory after phase={name}: {memory_line()}")
        return val

    params, store = timed(
        "build", lambda: init_offloaded_params(cfg, jax.random.PRNGKey(seed)))
    xla = timed("serve_xla", lambda: serve(
        params, store, cfg, prompts, max_new, ffn_impl="xla",
        paged_impl="xla"))
    check_served(xla, prompts, max_new)
    res["ref_rel_err"] = timed(
        "reference", lambda: check_reference(params, store, cfg, xla, 0,
                                             report))

    pal = timed("serve_pallas", lambda: serve(
        params, store, cfg, prompts, max_new, ffn_impl=pallas_impl,
        paged_impl=pallas_impl))
    check_served(pal, prompts, max_new)
    # greedy tokens as on the XLA kernels; a request whose tokens part
    # from them is held to the reference over its own tokens instead
    parted = [i for i, (a, b) in enumerate(zip(pal["tokens"], xla["tokens"]))
              if a != b]
    report(f"pallas vs xla: greedy tokens equal for "
           f"{len(prompts) - len(parted)} of {len(prompts)} requests")
    for i in parted:
        check_reference(params, store, cfg, pal, i, report)
    res["pallas_parted"] = parted

    layer_experts = (3 * cfg.d_model * cfg.expert_d_ff * cfg.num_experts
                     * jnp.dtype(cfg.dtype).itemsize)
    for name, out in (("serve_xla", xla), ("serve_pallas", pal)):
        s = out["stats"]
        report(f"{name}: {s['completed_requests']} requests, "
               f"{s['server_steps']} steps, hits {s['hits']}, misses "
               f"{s['misses']}, bytes moved to device "
               f"{s['bytes_transferred']}, largest live device array "
               f"{out['largest_live_array']} B")
        if out["largest_live_array"] > layer_experts:
            raise AssertionError("a device array outgrew one layer's experts")
    return res


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    cfg = smoke_config()
    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir}")
    print(f"config: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
          f"experts={cfg.num_experts} top{cfg.num_experts_per_tok} "
          f"d_ff={cfg.expert_d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    run(cfg, pallas_impl="pallas")
    print(f"memory at end: {memory_line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
