"""Pure-jnp oracle: the serving gather path, standalone.

Mirrors what ``repro.models.layers.apply_attention`` does on the paged
branch at decode time — materialize each sequence's logical KV view via
its block table, then run one masked fp32 softmax over the full view
width. The kernel must match this to flash-attention tolerances (the
online softmax reassociates the fp32 accumulation, nothing else).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q: jax.Array, k_arena: jax.Array,
                        v_arena: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, n_kv: int) -> jax.Array:
    """q: (B, n_q, D); arenas (n_blocks + 1, bs, n_kv * D);
    block_tables (B, max_blocks); lengths (B,). Returns (B, n_q, D)."""
    B, n_q, D = q.shape
    bs = k_arena.shape[1]
    M = block_tables.shape[1]
    group = n_q // n_kv
    scale = 1.0 / math.sqrt(D)

    def view(arena):
        flat = arena.reshape(arena.shape[0] * bs, n_kv, D)
        rows = (block_tables[:, :, None] * bs
                + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
        return flat[rows.reshape(B, M * bs)]            # (B, M*bs, nkv, D)

    k, v = view(k_arena), view(v_arena)
    qg = q.reshape(B, n_kv, group, D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(M * bs, dtype=jnp.int32)[None, :]
    valid = kv_pos < lengths[:, None]                   # (B, M*bs)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v)
    return out.reshape(B, n_q, D)
