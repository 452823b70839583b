"""deepseek-v2-236b [moe, MLA]. [arXiv:2405.04434]

The published config.json (huggingface.co/deepseek-ai/DeepSeek-V2): 60
layers, d_model 5120, 128 heads; MLA with a query LoRA of rank 1536,
kv_lora_rank 512, a 64-d rope key and 128-d nope/value heads, YaRN rope
scaling (factor 40 over 4096 original positions); the first layer dense
(d_ff 12288), then 2 shared + 160 routed experts of 1536, top-6 in the
top 3 of 8 groups, weights not renormalised and scaled by 16; vocab
102400, RMSNorm eps 1e-6. Decode caches the 512-d latent + 64-d rope
key per position (the whole point of MLA).
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,
    d_ff=12288,
    vocab_size=102_400,
    pos_emb="rope",
    rope_theta=10_000.0,
    use_mla=True,
    kv_lora_rank=512,
    qk_rope_dim=64,
    q_lora_rank=1536,
    rope_scaling={"type": "yarn", "factor": 40,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1,
                  "mscale": 0.707, "mscale_all_dim": 0.707},
    num_experts=160,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1536,
    first_k_dense_replace=1,
    topk_method="group_limited_greedy",
    n_group=8,
    topk_group=3,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    norm_eps=1e-6,
    long_context_window=8192,
    zero1=True,
    source="arXiv:2405.04434 (DeepSeek-V2); huggingface.co/deepseek-ai/DeepSeek-V2 config.json",
))
