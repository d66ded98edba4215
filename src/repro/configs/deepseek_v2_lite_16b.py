"""deepseek-v2-lite-16b [moe]: 27L d2048 16H, MLA kv_lora=512 (no q_lora),
YaRN rope (factor 40 over 4096 positions), layer 0 a dense SwiGLU of 10944,
layers 1-26 MoE: 64 routed experts of 1408, top-6 softmax gates without
renormalisation, plus 2 shared. vocab=102400, rms eps 1e-6, untied head.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=10944, vocab_size=102400, head_dim=128,
    rope_theta=1e4, norm_eps=1e-6, n_dense_layers=1,
    source="arXiv:2405.04434; hf",
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  norm_topk=False),
    mla=MLAConfig(kv_lora_rank=512, d_nope=128, d_rope=64, d_v=128,
                  yarn=YarnConfig(factor=40.0, original_max_position=4096,
                                  beta_fast=32.0, beta_slow=1.0,
                                  mscale=0.707, mscale_all_dim=0.707)),
    full_attention_only=True,
)
