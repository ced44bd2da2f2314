"""Paged-attention decode Pallas TPU kernel.

One query token per sequence attends over its paged KV cache *in place*:
each sequence's block table (the logical-block -> arena-block map kept by
the serving allocator) is scalar-prefetched into SMEM together with the
per-sequence KV lengths, and the grid's innermost axis walks the table,
DMA-ing K/V arena blocks straight into VMEM — the `(B, max_blocks *
block_size, n_kv, D)` logical view that ``repro.models.layers.paged_gather``
materializes per layer per tick is never built.

Grid: ``(B, n_kv_heads, max_blocks)``. Each program handles one
sequence's GQA head-group (the ``group = n_q // n_kv`` query heads that
share a KV head) against one KV block, carrying a flash-style online
softmax in (m, l, acc) VMEM scratch across the block axis. Blocks wholly
past the sequence length are skipped with ``@pl.when`` (their index-map
entry is clamped so the revisit-detection DMA elides the copy), and the
tail block masks columns ``>= length`` to -inf before the running max.

Layout: the TPU lowering wants the last two dimensions of every block
to be (8, 128)-divisible or whole. The arena is viewed as
``(n_blocks + 1, block_size, n_kv * D)`` — a free reshape of the
contiguous ``(.., n_kv, D)`` tail — so one KV head's block is the
``(block_size, D)`` tile at lane offset ``h * D`` (a strided DMA), and
the query as ``(B, n_kv, group, D)`` so a head-group block spans its
whole ``(group, D)`` tail. Neither depends on the GQA group size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
            max_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (group, D)
        k = k_ref[0].astype(jnp.float32)               # (bs, D)
        v = v_ref[0].astype(jnp.float32)               # (bs, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (group, bs)

        # length mask: decode attends to kv positions [0, length) only —
        # the tail block's unwritten rows get -inf (exact-0 after exp)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_size
        s = jnp.where(cols < length, s, NEG_INF)

        m_prev = m_ref[...]                            # (group, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == max_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Decode attention over a paged KV arena, gathering inside the kernel.

    q: (B, n_q, D) — one query token per sequence;
    k_arena, v_arena: (n_blocks + 1, block_size, n_kv, D) — the shared
    paged pool (last block is the allocator's scratch block);
    block_tables: (B, max_blocks) int32 — arena block per logical block;
    lengths: (B,) int32 — valid KV positions per sequence (entries past
    ``lengths[b]`` are masked; rows whose tables point at scratch simply
    produce ignored-but-finite outputs, exactly like the gather path).
    Returns (B, n_q, D).
    """
    B, n_q, D = q.shape
    block_size, n_kv = k_arena.shape[1], k_arena.shape[2]
    max_blocks = block_tables.shape[1]
    group = n_q // n_kv
    scale = 1.0 / math.sqrt(D)
    grid = (B, n_kv, max_blocks)

    def kv_index(b, h, j, tbl, lens):
        # out-of-length steps are compute-skipped; clamping them onto the
        # sequence's first block lets consecutive skipped steps reuse the
        # resident VMEM copy instead of DMA-ing dead blocks
        blk = jnp.where(j * block_size < lens[b], tbl[b, j], tbl[b, 0])
        return (blk, 0, h)

    def q_index(b, h, j, tbl, lens):
        return (b, h, 0, 0)

    n_rows = k_arena.shape[0]
    k_view = k_arena.reshape(n_rows, block_size, n_kv * D)
    v_view = v_arena.reshape(n_rows, block_size, n_kv * D)
    kernel = functools.partial(_kernel, scale=scale, block_size=block_size,
                               max_blocks=max_blocks)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, group, D), q_index),
                pl.BlockSpec((1, block_size, D), kv_index),
                pl.BlockSpec((1, block_size, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, group, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, lengths, q.reshape(B, n_kv, group, D), k_view, v_view)
    return out.reshape(B, n_q, D)
