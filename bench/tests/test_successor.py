"""The successor head: the reference's greedy choice after token x is
succ(x), the next token along the seeded cycle through the vocabulary,
so that a greedy answer is a run of distinct tokens on every seed
(reduced widths, on the CPU)."""
import jax
import numpy as np
import pytest

import spec as bspec

GQA = bspec.load_module(bspec.BENCH / "models" / "gqa_moe.py", "gqa_moe_s")
SMALL = {"hidden_size": 1024, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_experts_per_tok": 2, "num_local_experts": 8,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-05,
         "rope_theta": 10000.0, "vocab_size": 8192}


def successor(w) -> np.ndarray:
    """succ [V] of the weights' seeded cycle, as ``_make_params`` draws
    it."""
    V = w.dims.V
    order = np.asarray(jax.random.permutation(
        jax.random.fold_in(w.keys[0], 2), V))
    out = np.empty(V, np.int64)
    out[order] = np.roll(order, -1)
    return out


@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22])
def test_greedy_choice_is_the_successor(seed):
    w = GQA.Weights(SMALL, seed, 1.0)
    succ = successor(w)
    # one cycle through the whole vocabulary
    x, seen = 0, set()
    while x not in seen:
        seen.add(x)
        x = int(succ[x])
    assert len(seen) == w.dims.V
    seq = [int(np.random.default_rng(seed).integers(1, w.dims.V))]
    for _ in range(95):
        seq.append(int(succ[seq[-1]]))
    logits, _ = GQA.reference_logits(w, [seq], None, 0.0)
    follows = np.argmax(logits[0][:-1], -1) == np.asarray(seq[1:])
    assert follows.mean() >= 0.95
