"""deepseek-v2-lite-16b [moe]: 27L d=2048 16H MLA(kv_lora=512, no q_lora,
qk 128+64, v 128); layer 0 a dense SiLU MLP of 10944, layers 1-26 64
routed experts of 1408 (softmax, greedy top-6, gates not renormalised)
+ 2 shared; yarn rope (factor 40 over 4096 positions); RMSNorm eps 1e-6;
untied head over 102400.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]"""
from .base import ModelConfig, make_smoke

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=10944, vocab=102400, act="silu", gated=True, norm_eps=1e-6,
    n_dense_layers=1,
    n_experts=64, top_k=6, n_shared_experts=2, d_ff_expert=1408,
    norm_topk_prob=False,
    use_mla=True, kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128,
    v_head_dim=128,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)
SMOKE = make_smoke(CONFIG)
