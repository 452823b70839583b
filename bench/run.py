"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout that holds the program (``src/``). It
needs the TPU chips the cell asks for; with none it exits 2 and prints
no result. The last line of its standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit, which are also the last lines of its
standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import spec as bspec  # noqa: E402


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(spec: dict, cell: dict, res: dict, trace: bool) -> dict:
    """The run's result line from what ``driver.run`` measured."""
    import profile_reduce
    ctx = res["ctx"]
    metrics = {}
    for m in bspec.metrics_for(spec, cell, trace):
        v = bspec.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    lim = ctx.cfg["correct"]
    tokens = sum(len(t) for t in ctx.times.values())
    correct = (res["logit_err"] <= lim["max_logit_err"]
               and res["logit_gap"] <= lim["max_logit_gap"]
               and res["token_mismatch"] == 0
               and res["not_completed"] == 0 and res["preempted"] == 0
               and res["checked_tokens"] > 0 and tokens > 0)
    dev = res["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res["chips"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["not_completed"] + res["wrong_requests"],
            "metrics": metrics, "device": device}
    prof = ctx.profile
    if trace and prof is not None:
        device["busy_s"] = profile_reduce.busy_ns(prof) * 1e-9
        device["window_s"] = (prof.window[1] - prof.window[0]) * 1e-9
        line["breakdown"] = {"device_ops": profile_reduce.top_ops(prof),
                             "idle_gaps": profile_reduce.idle_gaps(prof)}
    line["checks"] = {
        "logit_err": {"value": res["logit_err"],
                      "limit": lim["max_logit_err"]},
        "logit_gap": {"value": res["logit_gap"],
                      "limit": lim["max_logit_gap"]},
        "token_mismatch": {"value": res["token_mismatch"], "limit": 0}}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    spec = bspec.benchmark()
    cell = bspec.workload(spec, args.workload)
    import driver
    try:
        res = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         t0=T0, report=_err)
    except driver.NoAccelerator as e:
        _err(f"bench: {e}")
        return 2
    line = result_line(spec, cell, res, bool(args.trace))
    ctx = res["ctx"]
    if args.trace:
        for l in ctx.expert_layers:
            _err(f"routing popularity, layer {l}: "
                 f"{ctx.popularity[l].tolist()}")
    _err(f"host peak RSS bytes {res['host_rss']}; set-up "
         f"{ctx.setup_s!r} s (weights built at {ctx.build_s!r} s)")
    gaps = [b - a for ts in ctx.times.values() for a, b in zip(ts, ts[1:])
            if a >= ctx.t_start and b <= ctx.t_end]
    if gaps:
        import numpy as np
        q = np.percentile(gaps, [50, 90, 95, 99]) * 1000.0
        _err(f"token gaps in the window: {len(gaps)}; p50 p90 p95 p99 ms "
             f"{q.tolist()}")
    if args.trace:
        plain = {m["name"]: bspec.metric_reader(m["name"]).read(ctx)
                 for m in bspec.metrics_for(spec, cell, False)}
        _err(f"untraced window's end-to-end metrics: {json.dumps(plain)}")
    _err(f"metrics: {json.dumps(line['metrics'])}")
    for name, c in line["checks"].items():
        _err(f"check {name}: value {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
