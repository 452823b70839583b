"""One run of a cell: build from the seed, warm up, measure, check.

The entry the window drives is the program's own serving loop:
``ContinuousOffloadServer.submit`` / ``.step`` over ``OffloadEngine``
(paged KV, greedy sampling, the cache policy and kernels the
configuration's ``serving`` block names). C clients each send their
next request when the last completes (``loadgen.Plan``).

Set-up builds the weights on the device from the seed, fills the host
expert store, compiles every shape the window uses (each block-table
width, each union-chunk width, each sampled row), and serves until every
client is past its prompt and every layer's expert cache is full. Then
the window runs for ``seconds``. A traced run goes on with a second,
traced window of ``TRACED_SECONDS``: the program runs in it as it does
untraced (nothing waits on the device that would not otherwise), and
the device-trace metrics read it, while the program counters and the
step's FLOP rate read the untraced window. After the windows, device
memory is read, the program is freed, and a sample of the served
requests is checked against the plain reference.

What the driver calls on the program, which the program has to offer
for any configuration:

- ``repro.core.expert_store.ExpertStore``: filled by ``fill_store``
  with ``put((layer, published expert id), {"w1", "w3", "w2"})`` for
  each layer and held expert of the model module's layout
  (``spec.expert_layout``), and given to ``ContinuousOffloadServer(...,
  store=store)``.
- ``srv.engine.caches``: per layer an ``ExpertCache``, or ``None`` for
  a layer with no routed experts in the store. The caches that exist
  are filled before the window (``slot_of``, ``n_slots``), their
  ``hits``, ``misses`` and ``bytes_transferred`` are counted over it,
  and the first one's ``gather`` warms every union width.
- ``srv.trace.steps``: per step and layer with routed experts,
  ``activated``, the experts the chip ran (the union, which
  ``moe_ffn_roofline`` counts), and ``request_ids``,
  ``request_token_idx`` and ``request_activated``: each row's routed
  experts as published ids, 0 to ``k`` of them (a row's other experts
  may sit on other chips). The check follows these routes; routing
  popularity counts them; the first such layer's
  ``request_token_idx`` gives the window's mean context.
- ``srv._step_rows``, ``tf._attn_decode_paged`` on layer 0 of
  ``params["layers"]`` (``oe._layer_slice``) to warm each block-table
  width, and ``oe._grouped_ffn`` to warm each union-chunk width.

What a model module (``models/<reference>.py``) provides is listed in
``spec.py``.
"""
from __future__ import annotations

import contextlib
import gc
import resource
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, List

import numpy as np

import loadgen
import profile_reduce
import spec as bspec

sys.path.insert(0, str(bspec.ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# A run needs one of these platforms; tests widen it to rehearse on CPU.
ACCEPTED_PLATFORMS = ("tpu",)
# JAX's persistent compile cache: one fixed directory inside the
# checkout, whatever the environment names (the cache keys on the path,
# and two checkouts share nothing). Tests set None: no cache.
CACHE_DIR = bspec.ROOT / ".jax_cache"
# A traced run's second window, all of it traced: the trace of a longer
# one takes more memory and time to read than a run may use.
TRACED_SECONDS = 12.0

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# recorded around every backend compile, a load from the persistent
# cache included; the two events below, inside it, say which it was
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


class NoAccelerator(RuntimeError):
    pass


def accelerator(chips: int):
    """The ``chips`` devices a cell asks for, or NoAccelerator."""
    devs = jax.devices()
    if devs[0].platform not in ACCEPTED_PLATFORMS:
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts programs lowered, loaded from the persistent compile cache
    and compiled anew (JAX's monitoring events), with the seconds each
    took and the names of those compiled anew, since construction."""

    def __init__(self):
        self.lowered = 0
        self.loaded = 0
        self.load_s = 0.0
        self.compiled = 0
        self.compile_s = 0.0
        self.names: List[str] = []
        self._hit = False

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self._hit = True

        def on_duration(event, duration, fun_name="?", **_):
            if event == LOWER_EVENT:
                self.lowered += 1
            elif event == COMPILE_EVENT:
                if self._hit:
                    self.loaded += 1
                    self.load_s += duration
                else:
                    self.compiled += 1
                    self.compile_s += duration
                    self.names.append(str(fun_name))
                self._hit = False
        self._listeners = (on_event, on_duration)
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def line(self) -> str:
        return (f"programs lowered {self.lowered}, loaded from the compile "
                f"cache {self.loaded} ({self.load_s:.3f} s), compiled "
                f"{self.compiled} ({self.compile_s:.3f} s)")

    def close(self):
        on_event, on_duration = self._listeners
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _caches(engine) -> list:
    """The engine's expert caches that exist (a layer with no routed
    experts in the store may have ``None``)."""
    return [c for c in engine.caches if c is not None]


def _cache_counts(engine) -> Dict[str, int]:
    caches = _caches(engine)
    return {"hits": sum(c.hits for c in caches),
            "misses": sum(c.misses for c in caches),
            "bytes": sum(c.bytes_transferred for c in caches)}


def fill_store(weights, layers, held):
    """The host expert store: for each layer in ``layers`` the experts
    ``weights.experts(layer)`` stacks, the i-th under the key (layer,
    ``held[i]``), its published id."""
    from repro.core.expert_store import ExpertStore
    store = ExpertStore()
    for l in layers:
        ex = jax.device_get(weights.experts(l))
        for i, e in enumerate(held):
            store.put((l, e), {k: v[i] for k, v in ex.items()})
        del ex
    return store


def popularity(records, n_layers: int, experts: int) -> np.ndarray:
    """Routing choices [n_layers, experts] over the trace's rows, by
    published id; a layer with no routed experts keeps a zero row."""
    out = np.zeros((n_layers, experts), np.int64)
    for r in records:
        for acts in r.request_activated:
            out[r.layer, [e for e in acts if e >= 0]] += 1
    return out


def _host_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def run(cell: dict, seed: int, seconds: float, trace: bool, *, t0: float,
        report=print, control: bool = False) -> dict:
    """Everything one run measures. ``t0`` is the process's start on the
    host clock (set-up is counted from it). With ``control`` the fp8
    reference is also read at the same positions (calibration only)."""
    from repro.configs.base import ModelConfig
    from repro.core import offload_engine as oe
    from repro.models import attention as attn
    from repro.models import transformer as tf
    from repro.serving import ContinuousOffloadServer

    devs = accelerator(cell["chips"])
    if CACHE_DIR is not None:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        # every program, however quick to compile, goes to the cache, so
        # a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc = CompileCounter()

    c = bspec.config(cell["config"])
    tr = bspec.traffic(cell["traffic"])
    model = bspec.model(c)
    mcfg = ModelConfig(**c["program"])
    sv = c["serving"]
    dims = model.dims(c)
    layers, held, experts = bspec.expert_layout(model, c)

    # ---- weights: non-expert tree on the device, experts to the host
    weights = model.Weights(c, seed, tr["routing_zipf_s"])
    params = weights.params()
    store = fill_store(weights, layers, held)
    t_built = time.perf_counter()
    report(f"host store: {len(store.keys())} experts, "
           f"{store.total_nbytes()} bytes")

    plan = loadgen.Plan(tr, seed, dims.V)
    C = plan.clients
    bs = sv["kv_block_size"]
    blocks = -(-plan.max_total() // bs)
    attn.PAGED_ATTN_IMPL = sv["paged_attn_impl"]
    srv = ContinuousOffloadServer(
        params, mcfg, store=store, cache_slots=sv["cache_slots"],
        max_batch=C, cache_len=plan.max_total(), policy=sv["policy"],
        ffn_impl=sv["ffn_impl"], kv_block_size=bs,
        kv_num_blocks=C * blocks, prefill_chunk=tr["prefill_chunk"])
    eng = srv.engine
    caches = _caches(eng)
    rows = srv._step_rows

    # ---- compile the shapes that depend on the traffic --------------
    p0 = oe._layer_slice(eng.params["layers"], 0)
    h0 = jnp.zeros((rows, 1, dims.d), eng.dtype)
    pos0 = jnp.zeros((rows,), jnp.int32)
    for T in range(1, blocks + 1):   # every block-table width
        bt = jnp.asarray(np.full((rows, T), srv.paged.sink, np.int32))
        jax.block_until_ready(tf._attn_decode_paged(
            p0, mcfg, h0, srv.state["layers"][0], pos0, bt))
    lg = jnp.zeros((rows, dims.V), jnp.float32)
    for b in range(rows):            # every row a token is sampled from
        jnp.argmax(lg[b], axis=-1).block_until_ready()
    del h0, lg

    # ---- closed loop -------------------------------------------------
    reqs: Dict[int, object] = {}
    owner: Dict[int, int] = {}
    times: Dict[int, List[float]] = {}
    served: Dict[int, list] = {}  # rid -> logits row of each output token
    open_rids: set = set()
    sent = [0]
    step_log: List[tuple] = []   # (start, end, active rows)
    shapes: List[tuple] = []     # (block-table width, rows) per traced call
    tracing = [False]            # host spans on (the traced window only)

    def submit(client: int):
        i = sent[0]
        sent[0] += 1
        _, n_out = plan.size(i)
        with _span(tracing[0], "bench.submit"):
            rid = srv.submit(plan.prompt(i), max_new=n_out)
        reqs[rid] = srv.queue[-1]
        owner[rid] = client
        times[rid] = []
        served[rid] = []
        open_rids.add(rid)

    def step():
        before = {rid: (len(reqs[rid].out), reqs[rid].slot)
                  for rid in open_rids}
        n_rec = len(srv.trace.steps)
        ta = time.perf_counter()
        with _span(tracing[0], "bench.step"):
            retired = srv.step()
        tb = time.perf_counter()
        recs = srv.trace.steps[n_rec:]
        # the row each request sampled from: its slot, or with chunked
        # prefill its last row (active rows come first, in trace order)
        last = {rid: i for i, rid in
                enumerate(recs[0].request_ids if recs else ())}
        for rid, (n, slot) in before.items():
            if len(reqs[rid].out) > n:
                times[rid].append(tb)
                row = slot if srv.prefill_chunk == 1 else last[rid]
                served[rid].append(srv.last_logits[row])
        step_log.append((ta, tb, len(recs[0].request_ids) if recs else 0))
        for rid in retired:
            open_rids.discard(rid)
            submit(owner[rid])

    for client in range(C):
        submit(client)
    for _ in range(10_000):
        if all(len(times[rid]) > 0 for rid in open_rids) and all(
                len(ca.slot_of) == ca.n_slots for ca in caches):
            break
        step()
    else:
        raise RuntimeError("the server never reached a full cache")

    x0 = jnp.zeros((rows, dims.d), eng.dtype)
    for ca in caches[:1]:            # every union-chunk width
        ids = sorted(ca.slot_of)
        for U in range(1, len(ids) + 1):
            w = ca.gather(ids[:U])
            jax.block_until_ready(oe._grouped_ffn(
                x0, w["w1"], w["w3"], w["w2"],
                jnp.zeros((rows, U), jnp.float32), impl=eng.ffn_impl))
    del x0, w

    report(f"set-up: {cc.line()}")
    if cc.names:
        import collections
        report("set-up compiled anew: " + ", ".join(
            f"{n} x{k}" for n, k in
            collections.Counter(cc.names).most_common(30)))

    def window(length: float) -> types.SimpleNamespace:
        """Serve for ``length`` seconds (the window closes at the end of
        the first step past it); what its metrics read."""
        gc.collect()
        counts0 = _cache_counts(eng)
        lowered0 = cc.lowered
        rec0, step0, shape0 = len(srv.trace.steps), len(step_log), len(shapes)
        t_start = time.perf_counter()
        with _span(tracing[0], profile_reduce.WINDOW_SPAN):
            while True:
                step()
                if step_log[-1][1] >= t_start + length:
                    break
        t_end = step_log[-1][1]
        counts1 = _cache_counts(eng)
        records = srv.trace.steps[rec0:]
        pos = [t for r in records if r.layer == layers[0]
               for t in r.request_token_idx]
        return types.SimpleNamespace(
            t_start=t_start, t_end=t_end, window_s=t_end - t_start,
            steps=step_log[step0:], shapes=shapes[shape0:], records=records,
            unions=[(r.layer, len(r.activated)) for r in records],
            counts={k: counts1[k] - counts0[k] for k in counts0},
            lowered_in_window=cc.lowered - lowered0,
            mean_context=float(np.mean(pos)) if pos else None)

    # ---- the window ----------------------------------------------------
    plain = window(seconds)
    report(f"window: {plain.window_s:.3f} s, {len(plain.steps)} steps, "
           f"programs lowered {plain.lowered_in_window}")
    traced = prof = None
    if trace:
        traced = _traced_window(eng, shapes, tracing, window)
        prof = traced.profile
        report(f"traced window: {traced.window_s:.3f} s, "
               f"{len(traced.steps)} steps, programs lowered "
               f"{traced.lowered_in_window}")
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    host_rss = _host_rss()
    report(f"in all: {cc.line()}; peak device bytes {mem_peak}; host peak "
           f"RSS {host_rss}")
    cc.close()

    ctx = types.SimpleNamespace(
        **{k: v for k, v in vars(plain).items() if k != "records"},
        setup_s=plain.t_start - t0, build_s=t_built - t0,
        chips=len(devs), times=times, expert_layers=layers,
        popularity=popularity(plain.records, dims.L, experts), dims=dims,
        cfg=c, model=model, slots=sv["cache_slots"], rows=rows,
        block_size=bs, peaks=bspec.peaks(devs[0].device_kind),
        traced=traced, profile=prof)
    if traced is not None:
        ctx.lowered_in_window += traced.lowered_in_window

    # ---- what the check compares: a sample of the served requests ---
    check = _pick(reqs, c["correct"]["check_tokens"], seed)
    routes = _routes(srv.trace, check, dims)
    sample = [(list(reqs[rid].tokens), len(reqs[rid].prompt), routes[rid],
               np.stack([np.asarray(x) for x in served[rid]]))
              for rid in check]
    served.clear()
    statuses = [r.status for r in reqs.values() if r.done]
    preempted = srv.kv_preemptions
    del srv, eng, caches, params, store, p0, pos0, plain
    gc.collect()

    result = {"ctx": ctx, "attempted": len(reqs), "preempted": preempted,
              "not_completed": sum(1 for s in statuses if s != "completed"),
              "memory_peak_bytes": int(mem_peak), "host_rss": host_rss,
              "device": devs[0], "chips": len(devs)}
    result.update(_compare(model, weights, c, sample, control, report))
    return result


def _traced_window(eng, shapes: list, tracing: list,
                   window) -> types.SimpleNamespace:
    """The traced window: ``window`` under the profiler, each engine call
    inside an ``engine.decode_tokens`` span with its shape recorded (no
    wait is added to the program), then the trace reduced."""
    real_decode = eng.decode_tokens

    def traced_decode(state, tokens, positions, *a, **kw):
        bt = kw.get("block_tables")
        shapes.append((0 if bt is None else int(bt.shape[1]),
                       int(tokens.shape[0])))
        with jax.profiler.TraceAnnotation("engine.decode_tokens"):
            return real_decode(state, tokens, positions, *a, **kw)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    eng.decode_tokens = traced_decode
    tracing[0] = True
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        w = window(TRACED_SECONDS)
    finally:
        jax.profiler.stop_trace()
        tracing[0] = False
        del eng.decode_tokens
    w.profile = profile_reduce.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return w


def _span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def _pick(reqs: Dict[int, object], budget: int, seed: int) -> List[int]:
    """Finished requests to check, drawn from the seed: the longest,
    then others in a seeded order until ``budget`` served tokens; where
    the finished ones fall short, requests still in flight (their
    tokens so far) make up the rest."""
    done = [rid for rid, r in reqs.items()
            if r.done and r.status == "completed" and r.out]
    live = [rid for rid, r in reqs.items() if not r.done and r.out]
    rng = np.random.default_rng([seed, 4])
    out: List[int] = []
    n = 0
    for group in (done, live):
        if not group:
            continue
        order = list(rng.permutation(group))
        longest = max(group, key=lambda rid: len(reqs[rid].out))
        order.remove(longest)
        for rid in [longest] + order:
            if n >= budget:
                return out
            out.append(int(rid))
            n += len(reqs[rid].out)
    return out


def _routes(trace, rids: List[int], dims) -> Dict[int, np.ndarray]:
    """Served experts [positions, L, k] of each request, from the
    program's routing trace, padded with -1 where a row lists fewer than
    ``k`` (or a layer has no routed experts)."""
    want = set(rids)
    rows: Dict[int, dict] = {rid: {} for rid in rids}
    for s in trace.steps:
        for rid, tok, acts in s.request_rows():
            if rid in want:
                rows[rid][(tok, s.layer)] = acts
    out = {}
    for rid, got in rows.items():
        n = 1 + max(t for t, _ in got) if got else 0
        r = np.full((n, dims.L, dims.k), -1, np.int64)
        for (tok, layer), acts in got.items():
            r[tok, layer, :len(acts)] = acts
        out[rid] = r
    return out


def _compare(model, weights, c: dict, sample, control: bool,
             report) -> dict:
    """The served logits of the sample against the float32 reference at
    the same positions: the widest relative L2 error of a served row,
    how many served tokens are not the first of their own row, and the
    widest gap by which a served token's reference logit lies below the
    reference's best (and the control's error and gap, where asked)."""
    lim = c["correct"]
    t = time.perf_counter()
    seqs = [toks[:-1] for toks, _, _, _ in sample]
    routes = [r[:len(s)] for (_, _, r, _), s in zip(sample, seqs)]
    ref, follow = model.reference_logits(weights, seqs, routes,
                                         lim["route_tie"])
    errs, gaps, refs, wrong = [], [], [], []
    for lg, (toks, n, _, got) in zip(ref, sample):
        want = lg[n - 1:n - 1 + len(got)]
        refs.append(want)
        errs.append(_rel_err(got, want))
        out = np.asarray(toks[n:n + len(got)])
        wrong.append(int(np.sum(np.argmax(got, -1) != out)))
        gaps.append(_gap(want, out))
    del ref
    n_tok = sum(len(e) for e in errs)
    mismatch = sum(wrong)
    out = {"logit_err": _widest(errs), "logit_gap": _widest(gaps),
           "token_mismatch": mismatch, "checked_tokens": n_tok,
           "checked_requests": len(sample),
           "wrong_requests": sum(
               1 for e, g, w in zip(errs, gaps, wrong)
               if w or _over(e, g, lim)),
           "follow": follow, "logit_err_q": _quantiles(errs)}
    report(f"reference: {len(sample)} requests, {n_tok} served tokens, "
           f"widest relative logit error {out['logit_err']!r} (rows' "
           f"quantiles {out['logit_err_q']}), "
           f"{mismatch} tokens not their row's first; widest gap of a "
           f"served token below the reference's best "
           f"{out['logit_gap']!r}; followed {follow['followed_flips']} "
           f"served routing choices off the reference top-k, by at most "
           f"{follow['followed_max_margin']!r}; "
           f"{time.perf_counter() - t:.3f} s")
    if control:
        ctl, _ = model.reference_logits(weights, seqs, None,
                                        lim["route_tie"], fp8=True)
        ce, cg = [], []
        for cl, w, (_, n, _, _) in zip(ctl, refs, sample):
            got = cl[n - 1:n - 1 + len(w)]
            ce.append(_rel_err(got, w))
            cg.append(_gap(w, np.argmax(got, -1)))
        out["control_err"] = _widest(ce)
        out["control_err_q"] = _quantiles(ce)
        out["control_gap"] = _widest(cg)
        out["control_wrong_requests"] = sum(
            1 for e, g in zip(ce, cg) if _over(e, g, lim))
        report(f"control (fp8 reference): widest relative logit error "
               f"{out['control_err']!r} (rows' quantiles "
               f"{out['control_err_q']}), widest gap "
               f"{out['control_gap']!r}")
    return out


def _over(err: np.ndarray, gap: np.ndarray, lim: dict) -> bool:
    """Whether a request's rows pass either limit."""
    return bool(len(err)) and (err.max() > lim["max_logit_err"]
                               or gap.max() > lim["max_logit_gap"])


def _widest(rows) -> float:
    return max((float(r.max()) for r in rows if len(r)), default=0.0)


def _quantiles(rows) -> dict:
    """Quantiles of the per-row errors of all requests together."""
    v = np.concatenate([r for r in rows if len(r)] or [np.zeros(1)])
    return {f"p{q}": float(np.percentile(v, q)) for q in (50, 90, 99)}


def _gap(want: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far the reference logit of each token lies below the row's
    best: want [n, V], tokens [n]."""
    return want.max(-1) - want[np.arange(len(tokens)), tokens]


def _rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row relative L2 error of logits [n, V]."""
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))
