"""The routing weights give Zipf popularity at ``routing_zipf_s`` 1.0
and near-uniform popularity at 0 (reduced widths, on the CPU; the
popularity is read from the reference's own router over random
tokens)."""
import jax.numpy as jnp
import numpy as np
import pytest

import spec as bspec

GQA = bspec.load_module(bspec.BENCH / "models" / "gqa_moe.py", "gqa_moe_t")
SMALL = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_experts_per_tok": 2, "num_local_experts": 8,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-05,
         "rope_theta": 10000.0, "vocab_size": 4096}


def popularity(zipf_s: float, seed: int = 2**31 + 3, n: int = 1024):
    """Routing choices [L, E] over n random tokens."""
    w = GQA.Weights(SMALL, seed, zipf_s)
    m = w.dims
    params = w.params()
    toks = np.random.default_rng(seed).integers(1, m.V, n)
    h = params["embed"].astype(jnp.float32)[jnp.asarray(toks)]
    counts = np.zeros((m.L, m.E))
    none = jnp.zeros((n, m.E), bool)
    for l in range(m.L):
        p = GQA._layer_weights(m, False, params["layers"], l)
        h, _, gates, _, _ = GQA._attend(m, False, p, h, none, 0.0, False)
        counts[l] = np.asarray((gates > 0).sum(0))
    return counts


def fitted_s(row):
    share = np.sort(row)[::-1] / row.sum()
    rank = np.log(np.arange(1, len(share) + 1))
    return -np.polyfit(rank, np.log(np.maximum(share, 1e-9)), 1)[0]


def test_calibration_hits_the_zipf_shares():
    m = GQA.zipf_calibration(8, 2, 1.0)
    assert np.all(np.diff(m) < 0)          # most popular first


@pytest.mark.parametrize("s", [1.0])
def test_zipf_popularity(s):
    counts = popularity(s)
    want = np.arange(1, 9) ** -s
    want /= want.sum()
    for row in counts:
        assert abs(fitted_s(row) - s) < 0.25
        share = np.sort(row)[::-1] / row.sum()
        assert np.max(np.abs(share - want)) < 0.05


def test_uniform_popularity_at_zero():
    for row in popularity(0.0):
        assert fitted_s(row) < 0.2
        assert row.max() / row.sum() < 0.17  # uniform 0.125; Zipf 1.0 0.37
