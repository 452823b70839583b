"""``run.py`` end to end on the CPU at a tiny size, with the platform
check steered by the test; with the timed path broken underneath,
``correct`` comes out false."""
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

import tiny
import spec as bspec

ARGS = ["--workload", "tiny.chat", "--seed", str(2**31 + 99),
        "--seconds", "2"]


def _run(monkeypatch, trace=0):
    import run
    tiny.on_cpu(monkeypatch)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(ARGS + ["--trace", str(trace)])
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bspec.BENCH / "run.py"), "--workload",
         "mixtral-8x7b.chat-1", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=bspec.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files (the platform check passed by the test), a run fails and
    prints no result."""
    import shutil
    shutil.copy(bspec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bspec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, 'bench'); import driver, run; "
            "driver.ACCEPTED_PLATFORMS = ('cpu',); sys.exit(run.main(["
            "'--workload', 'mixtral-8x7b.chat-1', '--seed', '1', "
            "'--seconds', '1', '--trace', '0']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr


def test_rehearsal_is_correct(monkeypatch):
    line = _run(monkeypatch)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    bench = json.loads((bspec.ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bspec.metrics_for(bench, tiny.cell(), False)}
    assert set(line["metrics"]) == want >= {"decode_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    chk = line["checks"]["logit_err"]
    assert chk["value"] < chk["limit"]
    assert list(line)[-1] == "checks"


def test_traced_rehearsal(monkeypatch):
    line = _run(monkeypatch, trace=1)
    assert line["correct"] is True
    assert "breakdown" in line and line["device"]["window_s"] > 0


def _altered_token(monkeypatch):
    """A token altered where it is produced: every third sampled token
    is the next id after the argmax."""
    from repro.serving.offload_serving import ContinuousOffloadServer
    real = ContinuousOffloadServer._sample
    count = [0]

    def sample(self, req, row):
        t = real(self, req, row)
        count[0] += 1
        return (t + 1) % self.cfg.vocab_size if count[0] % 3 == 0 else t
    monkeypatch.setattr(ContinuousOffloadServer, "_sample", sample)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: the KV written by each
    decode step is dropped."""
    from repro.core.offload_engine import OffloadEngine
    real = OffloadEngine.decode_tokens

    def decode(self, state, *a, **kw):
        kept = list(state["layers"])
        logits, st = real(self, state, *a, **kw)
        st["layers"][:] = kept
        return logits, st
    monkeypatch.setattr(OffloadEngine, "decode_tokens", decode)


def _half_batch(monkeypatch):
    """Half of the batch left out: every other row's expert outputs are
    dropped from the combine."""
    from repro.core import offload_engine as oe
    real = oe._combine_matrix

    def comb(*a, **kw):
        c = real(*a, **kw).copy()
        c[1::2] = 0.0
        return c
    monkeypatch.setattr(oe, "_combine_matrix", comb)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = _run(monkeypatch)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
