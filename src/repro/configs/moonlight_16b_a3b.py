"""Moonlight-16B-A3B (moonshotai, ``model_type`` deepseek_v3).
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]

27L (1 dense then 26 MoE), d_model=2048, MLA with 16 heads (no query
compression, kv_lora_rank=512, qk_nope 128 + qk_rope 64, v 128), dense
SwiGLU 11264, 64 routed experts of width 1408 with 6 per token (sigmoid
scores, selection-only bias, renormalised, scaled by 2.446) plus 2
shared experts, vocab 163840, untied embeddings, rope_theta 50000,
rms_norm_eps 1e-5.  Layer equations: DeepSeek-V2 (arXiv 2405.04434) for
MLA, DeepSeek-V3 (arXiv 2412.19437, §2.1.2) for the routing.
"""
from repro.configs.base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    head_dim=192,
    rope_theta=50000.0,
    attn_kind="causal",
    attn_impl="flash",
    tied_embeddings=False,
    norm_eps=1e-5,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense=1,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2,
                  aux_loss_weight=0.0, scoring="sigmoid", selection_bias=True,
                  routed_scaling=2.446, experts_held=64),
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)


def smoke() -> ArchConfig:
    """The same family at a CPU-test size: 1 dense + 1 MoE layer, 8
    experts with 2 per token, 2 shared experts, a 512-id vocabulary."""
    return ArchConfig(
        name="moonlight-smoke",
        arch_type="moe",
        n_layers=2,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        vocab=512,
        head_dim=24,
        rope_theta=50000.0,
        attn_kind="causal",
        q_block=16,
        tied_embeddings=False,
        norm_eps=1e-5,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        first_k_dense=1,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared_experts=2,
                      aux_loss_weight=0.0, scoring="sigmoid", selection_bias=True,
                      routed_scaling=2.446, experts_held=8),
        source="reduced moonlight family",
    )
