"""Moonlight-16B-A3B (moonshotai; ``model_type`` deepseek_v3) — 27L d=2048,
MLA 16 heads (kv_lora_rank 512, no q-LoRA, qk 128 + rope 64, v 128), one
leading dense layer of 11264, then 64 routed SwiGLU experts of 1408, top-6
by sigmoid score with the aux-loss-free selection bias (no group limit),
routed weights renormalised times 2.446, plus 2 shared experts; vocab
163840, untied, RMSNorm eps 1e-5, rope theta 50000.
[hf:moonshotai/Moonlight-16B-A3B config.json.] The bias speed and the
sequence-wise balance weight are not in the config: DeepSeek-V3's technical
report gives gamma 0.001 and alpha 0.0001."""

from repro.models.layers import MLAConfig
from repro.models.model import ModelConfig
from repro.models.moe import MoEConfig

BIAS_SPEED = 0.001
BALANCE_ALPHA = 0.0001


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, n_dense_layers=1, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=11264, vocab=163840, act="swiglu",
        norm="rmsnorm", norm_eps=1e-5, rope_theta=50000.0,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408,
                      score="sigmoid", routed_scale=2.446,
                      bias_speed=BIAS_SPEED, balance_alpha=BALANCE_ALPHA,
                      n_shared=2),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=3, n_dense_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab=128, act="swiglu", norm="rmsnorm", norm_eps=1e-5,
        rope_theta=50000.0,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(n_experts=16, top_k=6, d_expert=32, score="sigmoid",
                      routed_scale=2.446, bias_speed=BIAS_SPEED,
                      balance_alpha=BALANCE_ALPHA, n_shared=2),
        vocab_pad=16, remat=False,
    )
