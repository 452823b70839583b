"""Host spans inside the serving step (docs/traces.md, "Host spans").

A ``ContinuousOffloadServer`` run under the JAX profiler names its work
in nested ``jax.profiler.TraceAnnotation`` spans. Their arguments agree
with the program's own counters, and a run with the profiler on serves
the same tokens and routes the same way as one with it off. Each
scenario starts one profiler session, shared by the tests below.
"""
import dataclasses
import glob
import os

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core.faults import FaultPlan
from repro.models import transformer as tf
from repro.serving import ContinuousOffloadServer

# span -> the spans it may nest directly under (None: no program span)
PARENTS = {
    "server.step": (None,),
    "server.schedule": ("server.step",),
    "server.sample": ("server.step",),
    "engine.decode": ("server.step",),
    "engine.attention": ("engine.decode",),
    "engine.moe": ("engine.decode",),
    "engine.route": ("engine.moe",),
    "expert_cache.install": ("engine.moe", "engine.decode"),
    "engine.ffn": ("engine.moe",),
    "engine.logits": ("engine.decode",),
}
ARGS = {
    "server.step": {"step", "rows"},
    "server.schedule": set(),
    "server.sample": {"rid"},
    "engine.decode": {"rows"},
    "engine.attention": {"layer"},
    "engine.moe": {"layer"},
    "engine.route": {"layer"},
    "expert_cache.install": {"layer", "expert", "bytes", "demand"},
    "engine.ffn": {"layer", "experts", "rows"},
    "engine.logits": set(),
}
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 1, 4, 1, 5, 9, 2]]
WARM_STEPS = 2      # served before the profiler starts
TRACED_STEPS = 8

SCENARIOS = {
    # paged KV, prompt chunks of 2, speculative prefetch (installs
    # under engine.decode as well as under engine.moe)
    "paged-spec": dict(prefetch="spec", prefill_chunk=2),
    # dense KV, one token a step, abandoned demand fetches
    "dense-faults": dict(kv_layout="dense", faults=FaultPlan(
        seed=3, dma_failure_rate=0.6, max_retries=0)),
}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict
    parent: "Span" = None


def _counts(srv):
    cs = srv.engine.caches
    return {"misses": sum(c.misses for c in cs),
            "failures": sum(c.fetch_failures for c in cs),
            "prefetches": sum(c.prefetches for c in cs),
            "bytes": sum(c.bytes_transferred for c in cs)}


def _libtpu_mapped() -> bool:
    with open("/proc/self/maps") as f:
        return any("libtpu" in line for line in f)


def _serve(setup, kw, trace_dir=None):
    """Serve PROMPTS for WARM_STEPS + TRACED_STEPS steps, the last ones
    under the profiler when ``trace_dir`` is given. Returns the server,
    the per-step (rows, sampled rids) of the traced steps and the cache
    counters' deltas over them."""
    cfg, params = setup
    srv = ContinuousOffloadServer(params, cfg, cache_slots=2, policy="lru",
                                  max_batch=2, cache_len=32, **kw)
    reqs = {}
    for p in PROMPTS:
        rid = srv.submit(p, max_new=6)
        reqs[rid] = srv.queue[-1]
    for _ in range(WARM_STEPS):
        srv.step()
    c0 = _counts(srv)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    steps = {}
    try:
        for _ in range(TRACED_STEPS):
            before = {r.rid: len(r.out) for r in srv.slots if r is not None}
            n_rec = len(srv.trace.steps)
            number = srv.step_count
            srv.step()
            recs = srv.trace.steps[n_rec:]
            sampled = [rid for rid, n in before.items()
                       if len(reqs[rid].out) > n]
            steps[number] = (len(recs[0].request_ids) if recs else 0,
                             sampled)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    c1 = _counts(srv)
    return srv, steps, {k: c1[k] - c0[k] for k in c0}


def _tokens(srv):
    live = [r for r in srv.slots if r is not None] + list(srv.queue)
    return {r.rid: r.tokens for r in list(srv.finished.values()) + live}


def _spans(trace_dir):
    """The program's spans in the trace, each linked to the innermost
    program span that holds it."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats})
                   for e in line.events if e.name in PARENTS]
            lines.append(sorted(evs, key=lambda s: (s.start, -s.end)))
    out = []
    for evs in lines:
        stack = []
        for s in evs:
            while stack and not stack[-1].end >= s.end:
                stack.pop()
            s.parent = stack[-1] if stack else None
            stack.append(s)
        out += evs
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral-8x7b"), layers=2, d_model=64,
                  experts=4, vocab=128)
    cfg = dataclasses.replace(cfg, dtype="float32", num_experts_per_tok=2)
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=sorted(SCENARIOS))
def run(request, setup, tmp_path_factory):
    kw = SCENARIOS[request.param]
    plain, _, _ = _serve(setup, kw)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    tpu_before = _libtpu_mapped()
    traced, steps, delta = _serve(setup, kw, trace_dir)
    return dict(plain=plain, traced=traced, steps=steps, delta=delta,
                spans=_spans(trace_dir), cfg=setup[0],
                tpu_loaded=_libtpu_mapped() and not tpu_before)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_every_span_present_under_its_parent(run):
    spans = run["spans"]
    for name, parents in PARENTS.items():
        got = _named(spans, name)
        assert got, f"no {name} span"
        for s in got:
            p = s.parent.name if s.parent is not None else None
            assert p in parents, f"{name} under {p}"
    steps = _named(spans, "server.step")
    assert sorted(s.args["step"] for s in steps) == sorted(run["steps"])
    assert not run["tpu_loaded"]


def test_layer_args_cover_every_layer(run):
    L = run["cfg"].num_layers
    for name in ("engine.attention", "engine.moe", "engine.route",
                 "engine.ffn"):
        assert {s.args["layer"] for s in _named(run["spans"], name)} == \
            set(range(L)), name


def test_install_spans_match_cache_counters(run):
    inst = _named(run["spans"], "expert_cache.install")
    d = run["delta"]
    assert len(inst) == d["misses"] - d["failures"] + d["prefetches"]
    assert sum(s.args["bytes"] for s in inst) == d["bytes"]
    assert sum(s.args["demand"] == 0 for s in inst) == d["prefetches"]
    # a prefetch is installed outside the layer's MoE, a demand miss in it
    for s in inst:
        assert (s.parent.name == "engine.moe") == bool(s.args["demand"])
        if s.parent.name == "engine.moe":
            assert s.parent.args["layer"] == s.args["layer"]
    if run["traced"].faults is not None:
        assert d["failures"] > 0
    if run["traced"].engine.spec is not None:
        assert d["prefetches"] > 0


def test_step_rows_and_sampled_rids(run):
    for step in _named(run["spans"], "server.step"):
        rows, sampled = run["steps"][step.args["step"]]
        assert step.args["rows"] == rows
        kids = [s for s in run["spans"] if s.parent is step]
        assert [s.args["rid"] for s in kids
                if s.name == "server.sample"] == sampled
        dec = [s for s in kids if s.name == "engine.decode"]
        assert [s.args["rows"] for s in dec] == ([rows] if rows else [])


def test_ffn_args_match_the_union(run):
    """Each layer's FFN chunks together compute the layer's union less
    its dropped experts, over the step's full row width."""
    recs = run["traced"].trace.steps
    width = run["traced"]._step_rows
    ffn = _named(run["spans"], "engine.ffn")
    assert all(s.args["rows"] == width for s in ffn)
    by_moe = {}
    for s in ffn:
        by_moe.setdefault(id(s.parent), []).append(s.args["experts"])
    moes = _named(run["spans"], "engine.moe")
    want = [len(r.activated) - len(r.dropped) for r in recs[-len(moes):]]
    got = [sum(by_moe.get(id(m), [])) for m in moes]
    assert got == want


def test_every_arg_is_a_host_int(run):
    for s in run["spans"]:
        assert set(s.args) == ARGS[s.name], s.name
        assert all(type(v) is int for v in s.args.values()), (s.name, s.args)


def test_profiler_changes_no_token_or_route(run):
    a, b = run["plain"], run["traced"]
    assert _tokens(a) == _tokens(b)
    assert a.trace.steps == b.trace.steps
