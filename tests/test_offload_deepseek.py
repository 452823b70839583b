"""Offload engine on the DeepSeek-V2 family: MLA attention + 2 shared
(always-resident) + routed experts, top-6 — the arch-applicability
matrix's hardest MoE case (DESIGN.md §Arch-applicability)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import OffloadEngine
from repro.models import transformer as tf


@pytest.fixture(scope="module")
def dsv2_setup():
    cfg = reduced(get_config("deepseek-v2-236b"), layers=2, d_model=64,
                  experts=4)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_offloaded_mla_moe_matches_on_device(dsv2_setup):
    cfg, params = dsv2_setup
    assert cfg.use_mla and cfg.num_shared_experts == 1
    eng = OffloadEngine(params, cfg, cache_slots=3, policy="lfu")
    st = eng.init_state(1, 8)
    tok = jnp.asarray([[7]], jnp.int32)
    got, _ = eng.decode_token(st, tok, 0, 0)

    state = tf.init_decode_state(params, cfg, 1, 8)
    want, _ = tf.decode_step(params, cfg, state, tok, jnp.int32(0),
                             moe_path="dense")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_shared_experts_never_in_cache(dsv2_setup):
    """Shared experts are device-resident — only routed experts are
    keyed into the store/caches."""
    cfg, params = dsv2_setup
    eng = OffloadEngine(params, cfg, cache_slots=3, policy="lru")
    eng.generate([1, 2, 3], 8)
    # store holds exactly E routed experts of each MoE layer (the first
    # layer is dense)
    moe_layers = sum(map(cfg.has_moe, range(cfg.num_layers)))
    assert moe_layers == cfg.num_layers - 1
    assert len(eng.store.keys()) == moe_layers * cfg.num_experts


def test_offload_with_spec_prefetch_on_mla(dsv2_setup):
    cfg, params = dsv2_setup
    eng = OffloadEngine(params, cfg, cache_slots=3, policy="lru",
                        prefetch="spec")
    eng.generate([1, 2, 3], 10)
    s = eng.stats()
    assert s["spec_precision"] == pytest.approx(s["spec_recall"])
    assert s["hits"] + s["misses"] > 0


def test_working_set_larger_than_cache_streams(dsv2_setup):
    """top-k(=2 reduced) + guesses can exceed tiny caches; the engine
    streams in chunks and stays exact."""
    cfg, params = dsv2_setup
    eng1 = OffloadEngine(params, cfg, cache_slots=1, policy="lru")
    eng4 = OffloadEngine(params, cfg, cache_slots=4, policy="lru")
    out1 = eng1.generate([4, 5], 8)
    out4 = eng4.generate([4, 5], 8)
    assert out1 == out4
