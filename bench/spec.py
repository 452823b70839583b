"""Finds a cell's files by the names in ``BENCHMARK.json``.

A configuration is ``configs/<config>.json`` (its published sizes, the
cut, and the program's settings) with the plain reference it names in
``models/<reference>.py``; a traffic mix is ``traffic/<traffic>.json``;
a metric is ``metrics/<name>.py``. Nothing here knows a cell by name.

A model module provides, for the driver and the metrics:

- ``dims(c)``: a record with at least ``d``, ``E``, ``k``, ``V`` and
  ``L`` (the model's layers), and the widths its metrics read;
- ``token_flops(c, context)``: model FLOPs of one token;
- ``Weights(c, seed, zipf_s)``, with ``params()``, the non-expert tree
  as the program takes it, and ``experts(layer)``, the held experts of
  one layer stacked as ``w1``, ``w3`` [n, d, F] and ``w2`` [n, F, d];
- ``reference_logits(weights, seqs, served, tie, fp8=False)``: the
  plain reference, following served routes [S, L, k] in which -1 pads
  a row that lists fewer than ``k`` experts;
- optionally ``layout(c)``: see ``expert_layout``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that
    is not in ``peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model(cfg: dict):
    """The weight maker and plain reference a configuration names."""
    ref = cfg["reference"]
    return load_module(BENCH / "models" / f"{ref}.py", f"bench_model_{ref}")


def expert_layout(model, cfg: dict):
    """``(layers, held, experts)``: the indices of the layers whose
    routed experts live in the host store, the published ids of the
    experts this chip holds (in the order ``Weights.experts`` stacks
    them), and the published number of experts per layer. A model
    module's ``layout(cfg)`` gives them; without one, every layer holds
    every one of ``dims(cfg).E`` experts."""
    if hasattr(model, "layout"):
        return model.layout(cfg)
    d = model.dims(cfg)
    return list(range(d.L)), list(range(d.E)), d.E


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def metrics_for(spec: dict, cell: dict, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell["name"] in m["workloads"]]
