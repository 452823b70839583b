"""Multi-device correctness of the distributed paths (run in a
subprocess with 8 forced host devices so the real all-to-alls and
sharded einsums execute — the main pytest process must keep 1 device).

Covers the §Perf optimizations' exactness:
  * shard_map expert-parallel MoE == dense all-experts oracle
  * head-padded sharded attention == unsharded forward
  * sequence-sharded MLA latent cache decode == unsharded decode
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # quick loop: -m "not slow"


SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.models import moe as moe_lib
    from repro.models import transformer as tf
    from repro.models.sharding import sharding_ctx, param_pspecs, sanitize_spec

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = {"batch": ("data",), "model": "model", "heads": "model",
             "vocab": "model", "experts": "model", "capacity": "data",
             "shard_kv": True, "experts_mode": "ep", "_data_size": 2}

    # ---- 1. shard_map EP MoE vs dense oracle -------------------------
    cfg = reduced(get_config("mixtral-8x7b"), layers=2, d_model=64, experts=8)
    cfg = dataclasses.replace(cfg, dtype="float32", num_experts_per_tok=2,
                              capacity_factor=8.0)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    want, aux_want = moe_lib.moe_dense(p, cfg, x)
    with sharding_ctx(mesh, rules):
        got, aux = jax.jit(
            lambda p_, x_: moe_lib.moe_ep_shardmap(p_, cfg, x_))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("EP shard_map MoE OK")

    # ---- 2. head-padded sharded attention == unsharded ---------------
    cfg2 = dataclasses.replace(
        reduced(get_config("qwen1.5-32b"), layers=2, d_model=120, vocab=128),
        dtype="float32", num_heads=6, num_kv_heads=6, head_dim=20)
    params = tf.init_params(cfg2, jax.random.PRNGKey(2))
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 128)
    want = tf.prefill(params, cfg2, toks)
    with sharding_ctx(mesh, rules):
        got = jax.jit(lambda pp, tt: tf.prefill(pp, cfg2, tt))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    print("padded sharded attention OK")

    # ---- 3. sequence-sharded MLA decode == unsharded ------------------
    cfg3 = dataclasses.replace(reduced(get_config("deepseek-v2-236b"),
                                       layers=2, d_model=64),
                               dtype="float32")
    params3 = tf.init_params(cfg3, jax.random.PRNGKey(4))
    state = tf.init_decode_state(params3, cfg3, 2, 8)
    tok = jnp.asarray([[3], [5]], jnp.int32)
    want, _ = tf.decode_step(params3, cfg3, state, tok, jnp.int32(0))
    from repro.launch.specs import decode_state_pspecs
    with sharding_ctx(mesh, rules):
        sp = decode_state_pspecs(jax.eval_shape(lambda: state), rules)
        shardings = jax.tree.map(
            lambda s, v: NamedSharding(mesh, sanitize_spec(s, v.shape, mesh)),
            sp, state, is_leaf=lambda z: isinstance(z, P))
        state_sh = jax.device_put(state, shardings)
        got, _ = jax.jit(lambda pp, ss, t: tf.decode_step(
            pp, cfg3, ss, t, jnp.int32(0)))(params3, state_sh, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    print("MLA seq-sharded decode OK")
    print("ALL_DISTRIBUTED_OK")
""")


def test_distributed_paths_match_oracles():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_DISTRIBUTED_OK" in r.stdout
