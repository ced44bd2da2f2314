"""Jit wrapper matching the decode step's (B, 1, H, D) layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import counters, interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention


def paged_attention_decode(q, k_arena, v_arena, block_tables, lengths,
                           n_kv, interpret=None):
    """q: (B, 1, Hq, D); k/v arena: (n_blocks + 1, bs, n_kv * D);
    block_tables: (B, max_blocks); lengths: (B,) -> (B, 1, Hq, D)."""
    return _decode_jit(q, k_arena, v_arena, block_tables, lengths,
                       n_kv=n_kv, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("n_kv", "interpret"))
def _decode_jit(q, k_arena, v_arena, block_tables, lengths, n_kv,
                interpret):
    counters.record("paged_attention")
    B, S, Hq, D = q.shape
    assert S == 1, f"paged_attention is decode-only (S=1), got S={S}"
    of = paged_attention(q[:, 0], k_arena, v_arena,
                         jnp.asarray(block_tables, jnp.int32),
                         jnp.asarray(lengths, jnp.int32),
                         n_kv=n_kv, interpret=interpret)
    return of[:, None]
