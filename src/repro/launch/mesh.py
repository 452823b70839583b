"""Production mesh + per-architecture sharding rules.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") —
the "pod" axis is an extra data-parallel dimension across the DCN/ICI
boundary (batch shards over ("pod","data")).

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding rules place arrays with
    # with_sharding_constraint, which refuses the Explicit axes that
    # jax.make_mesh makes by default in recent JAX
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def sharding_rules(cfg, mesh, *, global_batch: Optional[int] = None,
                   baseline: bool = False) -> Dict:
    """Logical-axis → mesh-axis rules for this (arch, mesh, batch).

    - tiny archs (whisper) replicate weights entirely (pure DP);
    - "model" shards q-heads/ffn/vocab/ssm-inner; kv heads shard only
      when evenly divisible (else replicated — GQA kv counts are small);
    - MoE experts shard on "model" when E % model == 0 (expert
      parallelism, all-to-all dispatch), else expert weights shard their
      ffn dim (tensor parallelism — e.g. Mixtral's 8 experts on a
      16-way axis);
    - batch shards over ("pod","data") when divisible, else replicates
      (long_500k's global_batch=1).
    """
    m = mesh.shape["model"]
    b_axes = batch_axes(mesh)
    n_batch_shards = 1
    for a in b_axes:
        n_batch_shards *= mesh.shape[a]

    tiny = cfg.d_model * cfg.num_layers < 16_384  # whisper-tiny class
    model_ax = None if tiny else "model"

    batch_rule: Optional[Tuple[str, ...]] = b_axes
    if global_batch is not None and global_batch % n_batch_shards != 0:
        batch_rule = None

    rules = {
        "batch": batch_rule,
        "model": model_ax,
        "heads": model_ax,
        "vocab": model_ax,
        "experts": model_ax,
        "capacity": None if tiny else "data",
        # caches/projections are head-padded to the axis size (see
        # attention._head_padding) so kv shards whenever the padded
        # count divides; constrain() still drops non-dividing dims.
        "shard_kv": bool(model_ax),
        "experts_mode": "ep" if (cfg.num_experts and model_ax
                                 and cfg.num_experts % m == 0) else "tp",
        "_data_size": mesh.shape["data"],
    }
    if baseline:
        # paper-faithful / pre-optimization configuration (§Perf):
        # pjit-scatter MoE dispatch, no head padding (replicated attn for
        # H % 16 != 0), replicated MLA latent cache
        rules.update({"pad_heads": False, "moe_shardmap": False,
                      "mla_seq_shard": False,
                      "shard_kv": bool(model_ax) and cfg.num_kv_heads % m == 0})
    return rules
