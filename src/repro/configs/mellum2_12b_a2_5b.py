"""mellum2-12b-a2.5b [moe] — 28L hidden=2304, GQA 32 q / 4 kv heads of
128, every MLP sparse: 64 experts of width 896, top-8 softmax router with
renormalised gates, no shared expert; vocab 98,304, untied.  Layers
repeat (sliding, sliding, sliding, full): SWA(1024) with RoPE θ 5e5, and
full attention with YaRN RoPE (θ 5e5, factor 16 over 8192 positions,
β_fast 32, β_slow 1, attention factor 1.2772588722239782).  As in the
paper's hybrid, MoBA (block 128, top_k 8) takes the full-attention
layers' place.  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]"""
from repro.configs.base import (AttentionConfig, MoBAConfig, ModelConfig,
                                MoEConfig, YarnRope)

YARN = YarnRope(factor=16.0, original_max_position_embeddings=8192,
                beta_fast=32.0, beta_slow=1.0,
                attention_factor=1.2772588722239782)


def get_config(block_size: int = 128, top_k: int = 8,
               key_conv_width: int = 0) -> ModelConfig:
    moba = MoBAConfig(block_size=block_size, top_k=top_k,
                      key_conv_width=key_conv_width)
    return ModelConfig(
        name="mellum2-12b-a2.5b", family="moe",
        num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4,
        head_dim=128, d_ff=7168, vocab_size=98304, max_seq_len=131072,
        tie_embeddings=False, rms_norm_eps=1e-6,
        moe=MoEConfig(num_experts=64, top_k=8, expert_d_ff=896),
        attention=AttentionConfig(kind="moba", moba=moba, window=1024,
                                  rope_theta=5e5, rope_on_moba=True,
                                  moba_yarn=YARN),
        layer_pattern=("swa", "swa", "swa", "moba"))


def get_smoke_config(**kw) -> ModelConfig:
    """2 layers (one SWA, one MoBA), 8 experts of which 2 are held."""
    moba = MoBAConfig(block_size=16, top_k=2,
                      key_conv_width=kw.get("key_conv_width", 0))
    return ModelConfig(
        name="mellum2-smoke", family="moe",
        num_layers=2, d_model=64, num_heads=8, num_kv_heads=1, head_dim=16,
        d_ff=128, vocab_size=256,
        moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                      experts_held=2),
        attention=AttentionConfig(
            kind="moba", moba=moba, window=32, rope_theta=5e5,
            rope_on_moba=True,
            moba_yarn=YarnRope(factor=4.0,
                               original_max_position_embeddings=16)),
        layer_pattern=("swa", "moba"), dtype="float32")
