"""chip_smoke.py's phases at a tiny fp32 size on the CPU, in-process
(Pallas kernels in interpret mode), and its refusal to run without a
TPU."""
import dataclasses
import gc
import importlib.util
import os

import numpy as np
import pytest

from conftest import tiny

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


@pytest.fixture(scope="module")
def tiny_cfg():
    return dataclasses.replace(tiny("mixtral-8x7b", experts=8),
                               num_experts_per_tok=2)


def test_all_phases_pass_at_tiny_size(tiny_cfg):
    # the run checks the largest live device array: free what earlier
    # tests in this process left in reference cycles first
    gc.collect()
    lines = []
    res = cs.run(tiny_cfg, pallas_impl="pallas_interpret",
                 prompt_lens=(3, 5), max_new=3, report=lines.append)
    # fp32 served path vs fp32 reference: only summation order differs
    assert res["ref_rel_err"] < 1e-4
    assert res["pallas_parted"] == []
    for phase in ("build", "serve_xla", "reference", "serve_pallas"):
        assert any(f"phase={phase} " in ln for ln in lines), phase
    assert not any("sim_" in ln for ln in lines)


@pytest.fixture(scope="module")
def served(tiny_cfg):
    import jax
    params, store = cs.init_offloaded_params(tiny_cfg, jax.random.PRNGKey(1))
    prompts = cs.make_prompts(tiny_cfg, (4, 2), seed=1)
    out = cs.serve(params, store, tiny_cfg, prompts, 3, ffn_impl="xla",
                   paged_impl="xla")
    return params, store, prompts, out


def test_serve_returns_tokens_logits_and_routes(tiny_cfg, served):
    _, _, prompts, out = served
    cs.check_served(out, prompts, 3)
    for p, toks, lg, rt in zip(prompts, out["tokens"], out["logits"],
                               out["routes"]):
        assert len(toks) == len(p) + 3
        assert lg.shape == (len(toks), tiny_cfg.vocab_size)
        assert rt.shape == (len(toks), tiny_cfg.num_layers, 2)
        # top-2 of 8 distinct experts at every (position, layer)
        assert (rt[..., 0] != rt[..., 1]).all()
    with pytest.raises(AssertionError):
        cs.check_served(out, prompts, 4)
    bad = dict(out, logits=[out["logits"][0] * np.nan] + out["logits"][1:])
    with pytest.raises(AssertionError):
        cs.check_served(bad, prompts, 3)


def test_reference_check_catches_wrong_logits_and_routes(tiny_cfg, served):
    params, store, _, out = served
    assert cs.check_reference(params, store, tiny_cfg, out, 0,
                              lambda s: None) < 1e-4
    off = dict(out, logits=[out["logits"][0] * 1.1] + out["logits"][1:])
    with pytest.raises(AssertionError, match="off the float32 reference"):
        cs.check_reference(params, store, tiny_cfg, off, 0, lambda s: None)
    _, router = cs.reference_logits(params, store, tiny_cfg,
                                    out["tokens"][0], out["routes"][0])
    assert cs.check_routes(out["routes"][0], router) == 0
    worst = np.argsort(router, axis=-1)[..., :2].transpose(1, 0, 2)
    with pytest.raises(AssertionError, match="trail the top-2"):
        cs.check_routes(worst, router)


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert cs.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
