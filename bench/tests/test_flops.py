"""Operation and byte counts against hand counts at published shapes."""
import flops

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_moe_ffn_at_mixtral_shapes():
    # 4 experts of d 4096, F 14336 over a 64-row step
    f, b = flops.moe_ffn_call(4, 64, 4096, 14336)
    assert f == 4 * 64 * (2 * 4096 * 14336 * 3)          # 90.2 GFLOP
    assert b == (4 * 3 * 4096 * 14336 * 2                  # bf16 weights
                 + 4 * 64 * 4096 * 2 + 4 * 64 * 4096 * 4)  # x in, fp32 out
    t, bound = flops.least_time(f, b, PEAKS)
    assert bound == "bytes" and abs(t - b / 819e9) < 1e-15


def test_moe_ffn_at_deepseek_shapes():
    # 6 experts of d 5120, F 1536 over 512 rows: compute-bound
    f, b = flops.moe_ffn_call(6, 512, 5120, 1536)
    assert f == 6 * 512 * 6 * 5120 * 1536
    assert b == 6 * 3 * 5120 * 1536 * 2 + 6 * 512 * 5120 * 6
    assert flops.least_time(f, b, PEAKS)[1] == "flops"


def test_paged_attention_at_mixtral_shapes():
    # 64 rows, 8 blocks of 16: 32 q heads over 8 kv heads of 128
    f, b = flops.paged_attention_call(64, 8, 16, 32, 8, 128)
    keys = 64 * 8 * 16
    assert f == keys * 32 * 128 * 2 * 2
    assert b == keys * 8 * 128 * 2 * 2 + 64 * 32 * 128 * 2 * 2 + 64 * 8 * 4


def test_token_flops_mixtral_layer():
    per = flops.gqa_moe_token_flops(4096, 32, 8, 128, 14336, 8, 2, 32000, 1,
                                    0)
    proj = 2 * 4096 * (32 * 128 + 2 * 8 * 128 + 32 * 128)
    moe = 2 * 4096 * 8 + 2 * 3 * 2 * 4096 * 14336
    assert per == proj + moe + 2 * 4096 * 32000
    # one more position of context adds 2 x 2 x 32 x 128 per layer
    assert flops.gqa_moe_token_flops(4096, 32, 8, 128, 14336, 8, 2, 32000,
                                     1, 1) - per == 4 * 32 * 128
