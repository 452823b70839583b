"""Logical-axis sharding context (MaxText-style rules, minimal).

Model code calls ``constrain(x, "batch", None, "heads", None)`` with
logical axis names; the launcher installs a mesh + rules mapping logical
names to mesh axes. With no context installed everything is a no-op, so
smoke tests and the offload engine run single-device untouched.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_CTX = {"mesh": None, "rules": {}}


def set_sharding(mesh, rules: dict) -> None:
    _CTX["mesh"] = mesh
    _CTX["rules"] = dict(rules)


def clear_sharding() -> None:
    _CTX["mesh"] = None
    _CTX["rules"] = {}


@contextmanager
def sharding_ctx(mesh, rules: dict):
    old = (_CTX["mesh"], _CTX["rules"])
    set_sharding(mesh, rules)
    try:
        yield
    finally:
        _CTX["mesh"], _CTX["rules"] = old


def active_mesh():
    return _CTX["mesh"]


def active_rules() -> dict:
    return _CTX["rules"]


def padded_count(n: int) -> int:
    """Round a head count up to the model-axis size so it shards
    evenly (zero-padded heads; exact because wo's padded rows are 0).
    Identity when no mesh/model rule is active or n already divides."""
    mesh = _CTX["mesh"]
    m = _CTX["rules"].get("model")
    if mesh is None or m is None or not _CTX["rules"].get("pad_heads", True):
        return n
    size = mesh.shape[m]
    return -(-n // size) * size


def logical_to_spec(*axes) -> P:
    rules = _CTX["rules"]
    return P(*[rules.get(a) if a is not None else None for a in axes])


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop mesh axes from dims they don't evenly divide (jit arg
    shardings require exact divisibility)."""
    out = []
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for dim, axis in zip(shape, parts):
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        out.append(axis if dim % total == 0 else None)
    return P(*out)


def constrain(x, *axes):
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    spec = sanitize_spec(logical_to_spec(*axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------
# Parameter partition specs, derived from param-tree key paths.
# ---------------------------------------------------------------------
def _spec_for(path: str, ndim: int, rules: dict) -> P:
    """Map a parameter path (joined key names) + rank to a PartitionSpec.

    Stacked (scanned) parameter trees have extra leading layer dims; the
    returned spec is padded with leading Nones to match ``ndim``.
    """
    m = rules.get("model")
    ep = rules.get("experts_mode", "ep")
    name = path.split("/")[-1]

    def base() -> tuple:
        # attention
        if name in ("wq", "wk", "wv"):
            return (None, m, None) if name == "wq" or rules.get("shard_kv", True) \
                else (None, None, None)
        if name == "wo":
            return (m, None, None)
        if name in ("bq", "bk", "bv"):
            return (m, None) if (name == "bq" or rules.get("shard_kv", True)) \
                else (None, None)
        if name in ("w_kb", "w_vb", "w_qb"):
            return (None, m, None)
        if name in ("w_dkv", "w_kr"):
            return (None, None)
        # mlp / moe
        if name in ("w1", "w3"):
            if "experts" in path:
                # stacked experts [E, d, ff]
                return (m, None, None) if ep == "ep" else (None, None, m)
            return (None, m)
        if name == "w2":
            if "experts" in path:
                return (m, None, None) if ep == "ep" else (None, m, None)
            return (m, None)
        if name in ("b1",):
            return (m,)
        if name in ("b2",):
            return (None,)
        if name == "router":
            return (None, None)
        # ssm
        if name in ("in_proj", "in_z", "in_xbc", "in_dt"):
            return (None, m)
        if name == "out_proj":
            return (m, None)
        if name == "conv_w":
            return (None, m)
        if name == "conv_b":
            return (m,)
        if name == "norm" and ndim >= 1:
            return (None,)
        # embeddings
        if name == "embed":
            return (None, m)
        if name == "unembed":
            return (None, m)
        return tuple()

    b = [a for a in base()]
    pad = ndim - len(b)
    if pad < 0:
        b = b[-ndim:] if ndim > 0 else []
        pad = 0
    return P(*([None] * pad + b))


def param_pspecs(params, rules: Optional[dict] = None, mesh=None):
    """PartitionSpec pytree mirroring ``params`` (works on arrays or
    ShapeDtypeStructs). If ``mesh`` given, specs are divisibility-
    sanitized against leaf shapes."""
    rules = rules if rules is not None else _CTX["rules"]

    def f(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
        p = "/".join(str(k) for k in keys)
        ndim = len(leaf.shape)
        spec = _spec_for(p, ndim, rules)
        if mesh is not None:
            spec = sanitize_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(f, params)


def param_shardings(params, mesh=None, rules: Optional[dict] = None):
    mesh = mesh if mesh is not None else _CTX["mesh"]
    specs = param_pspecs(params, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
