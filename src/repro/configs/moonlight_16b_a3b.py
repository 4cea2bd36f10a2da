"""Moonlight-16B-A3B — DeepSeek-V3 block: latent attention (MLA) with no q
compression, one leading dense layer, then 26 layers of 64 fine-grained
routed experts (sigmoid router, top-6 of the bias-corrected scores) beside
two shared experts; untied embeddings.

[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]

``CONFIG`` is the published model.  ``CUT_CONFIG`` is one chip's share of
a deployment in which 8 chips share each layer by expert parallelism: 8 of
the 64 routed experts and 1/8 of the vocabulary, with attention, the shared
experts, the router and the dense layer whole; the first 10 of the 27
layers, the rest lying on further chips as pipeline stages.
"""
from repro.configs.base import ArchConfig, register

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="mla_moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    head_dim=128 + 64, d_ff=11264, vocab_size=163840,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_experts=64, top_k=6, moe_d_ff=1408,
    n_shared_experts=2, first_k_dense=1,
    routed_scaling_factor=2.446, rope_theta=50_000.0, norm_eps=1e-5,
    tie_embeddings=False, max_seq_len=8192,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
)

CUT_CONFIG = CONFIG.replace(n_layers=10, n_experts_held=8,
                            vocab_size=163840 // 8)

SMOKE_CONFIG = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16 + 8,
    d_ff=128, vocab_size=512, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_experts=8, top_k=2, moe_d_ff=32,
    n_experts_held=4, max_seq_len=256)

register(CONFIG, SMOKE_CONFIG)
