"""A tiny configuration and traffic mix for rehearsing a run on the CPU,
and the switches that let a test run the harness there."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spec as bspec  # noqa: E402

TINY_CONFIG = {
    "reference": "gqa_moe",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts_per_tok": 2, "num_local_experts": 8,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "vocab_size": 512,
    "program": {"name": "tiny-moe", "family": "moe", "num_layers": 2,
                "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                "pos_emb": "rope", "rope_theta": 10000.0, "num_experts": 8,
                "num_experts_per_tok": 2, "moe_d_ff": 128, "norm_eps": 1e-05,
                "dtype": "bfloat16"},
    "serving": {"cache_slots": 4, "policy": "lfu", "ffn_impl": "xla",
                "paged_attn_impl": "xla", "kv_block_size": 16},
    "correct": {"max_logit_err": 0.05, "max_logit_gap": 0.05,
                "route_tie": 0.1,
                "check_tokens": 40},
}

TINY_TRAFFIC = {
    "clients": 2, "pool": 6,
    "prompt_len": {"mean": 15, "sd": 15, "min": 4, "max": 40},
    "output_len": {"mean": 7, "sd": 4.5, "min": 3, "max": 12},
    "prefill_chunk": 4, "routing_zipf_s": 1.0,
}

TINY_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1e9}


def cell(name: str = "tiny.chat") -> dict:
    return {"name": name, "config": "tiny", "traffic": "chat", "chips": 1}


def on_cpu(monkeypatch, config=None, traffic=None):
    """Steer the harness to rehearse on the CPU: accept its platform,
    give its device kind peaks, and serve the tiny cell."""
    import driver
    monkeypatch.setattr(driver, "ACCEPTED_PLATFORMS", ("tpu", "cpu"))
    # a rehearsal writes no compile cache into the checkout
    monkeypatch.setattr(driver, "CACHE_DIR", None)
    monkeypatch.setattr(bspec, "peaks", lambda kind: TINY_PEAKS)
    monkeypatch.setattr(bspec, "config",
                        lambda name: copy.deepcopy(config or TINY_CONFIG))
    monkeypatch.setattr(bspec, "traffic",
                        lambda name: copy.deepcopy(traffic or TINY_TRAFFIC))
    bench = json.loads((bspec.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(cell())
    monkeypatch.setattr(bspec, "benchmark", lambda: bench)
    return bench
