"""phi3-medium-14b [dense]: 40L d=5120 40H (GQA kv=10) d_ff=17920 v=32064 —
RoPE, SwiGLU, GQA.

Source: microsoft/Phi-3-medium-128k-instruct ``config.json``
(hidden_size 5120, intermediate_size 17920, num_hidden_layers 40,
num_attention_heads 40, num_key_value_heads 10, vocab_size 32064,
rope_theta 10000, max_position_embeddings 131072) and arXiv:2404.14219.

Departures: the published model scales RoPE with ``longrope`` (short
and long factors) beyond its 4096-token original context; that scaling
is not modelled, so positions use plain RoPE at theta 10000. RMSNorm
epsilon is the model code's 1e-6 (published 1e-5).
"""
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig)


def config() -> ModelConfig:
    attn = AttentionSpec(n_q=40, n_kv=10, head_dim=128)
    mlp = MLPSpec(d_ff=17920, act="silu", gated=True)
    return ModelConfig(
        name="phi3-medium-14b", d_model=5120, vocab=32064,
        pattern=(LayerSpec(attn, mlp),), n_periods=40,
        norm="rmsnorm", scan_layers=True, remat=True,
        arch_class="dense", max_seq=131072)
