"""Jit-ready wrappers for the grouped (all-experts-in-one-launch) and
ragged (routed-tokens-only) block-sparse GEMMs, plus plan stacking from
independent per-expert plans.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import counters, interpret_mode
from repro.kernels.grouped_block_sparse.kernel import (
    grouped_block_sparse_matmul, quant_grouped_block_sparse_matmul,
    quant_ragged_block_sparse_matmul, ragged_block_sparse_matmul)


def stack_expert_plans(counts_e, indices_e) -> tuple:
    """Stack per-expert ``plan_blocks`` outputs into the rectangular
    (counts (E, nN), indices (E, nN, max_nnz)) arrays the grouped kernel
    consumes: index rows are edge-padded to the max ``max_nnz`` across
    experts (padded steps are masked on ``counts``)."""
    counts_e = [np.asarray(c) for c in counts_e]
    indices_e = [np.asarray(i) for i in indices_e]
    max_nnz = max(idx.shape[1] for idx in indices_e)
    indices_e = [np.pad(idx, ((0, 0), (0, max_nnz - idx.shape[1])),
                        mode="edge") for idx in indices_e]
    return np.stack(counts_e), np.stack(indices_e)


# Above this many slot rows the x panel stops fitting comfortably in
# VMEM next to the weight tiles; fall back to tiling M by the plan block.
PANEL_ROWS_MAX = 1024

# M-tile height of the ragged kernel: one sublane tile (covers bf16's
# (16, 128) and f32's (8, 128)), so per-expert segment padding wastes at
# most 15 rows per occupied expert.
RAGGED_BLOCK_ROWS = 16


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _grouped_matmul_jit(x, w, counts, indices, work, block_m, block_k,
                        block_n, interpret):
    return grouped_block_sparse_matmul(x, w, counts, indices, work=work,
                                       block_m=block_m, block_k=block_k,
                                       block_n=block_n, interpret=interpret)


def grouped_blocksparse_matmul(x, w, counts, indices, block_m=None,
                               block_k=128, block_n=128, interpret=None,
                               row_live=None):
    """Public op: y[e] = x[e] @ w[e] for all experts in one launch,
    visiting nonzero weight blocks only. ``block_m=None`` keeps each
    expert's whole M panel resident (the decode-shaped default — every
    weight tile is read exactly once per launch); pass an explicit
    ``block_m`` to tile M for prefill-sized batches.

    ``row_live`` (optional, (E, M) bool): per-row occupancy from the
    router. (expert, M-block) pairs with no live row skip compute and
    elide their DMAs; rows routing later gathers stay bitwise-identical
    to the unmasked launch. None computes every block."""
    if block_m is None:
        block_m = x.shape[1]
    E = x.shape[0]
    n_mblocks = x.shape[1] // block_m
    if row_live is None:
        work = jnp.ones((E, n_mblocks), jnp.int32)
        experts_computed = E
    else:
        work = row_live.reshape(E, n_mblocks, block_m).any(-1)
        experts_computed = work.any(-1).sum()
        work = work.astype(jnp.int32)
    counters.record("grouped_block_sparse")
    counters.record_concrete("grouped_block_sparse_experts_computed",
                             experts_computed)
    return _grouped_matmul_jit(x, w, counts, indices, work, block_m,
                               block_k, block_n, interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _ragged_matmul_jit(x, w, counts, indices, tile_expert, block_m, block_k,
                       block_n, interpret):
    return ragged_block_sparse_matmul(x, w, counts, indices, tile_expert,
                                      block_m=block_m, block_k=block_k,
                                      block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _quant_grouped_matmul_jit(x, tiles, counts, indices, slots, scales,
                              work, block_m, block_k, block_n, interpret):
    return quant_grouped_block_sparse_matmul(
        x, tiles, counts, indices, slots, scales, work=work,
        block_m=block_m, block_k=block_k, block_n=block_n,
        interpret=interpret)


def quant_grouped_blocksparse_matmul(x, tiles, counts, indices, slots,
                                     scales, block_m=None, block_k=128,
                                     block_n=128, interpret=None,
                                     row_live=None):
    """Public op: the grouped launch with kept tiles stored int8 + pow2
    scales (same panel default and ``row_live`` occupancy masking as
    :func:`grouped_blocksparse_matmul`)."""
    if block_m is None:
        block_m = x.shape[1]
    E = x.shape[0]
    n_mblocks = x.shape[1] // block_m
    if row_live is None:
        work = jnp.ones((E, n_mblocks), jnp.int32)
        experts_computed = E
    else:
        work = row_live.reshape(E, n_mblocks, block_m).any(-1)
        experts_computed = work.any(-1).sum()
        work = work.astype(jnp.int32)
    counters.record("grouped_block_sparse_quant")
    counters.record_concrete("grouped_block_sparse_quant_experts_computed",
                             experts_computed)
    return _quant_grouped_matmul_jit(x, tiles, counts, indices, slots,
                                     scales, work, block_m, block_k,
                                     block_n, interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _quant_ragged_matmul_jit(x, tiles, counts, indices, slots, scales,
                             tile_expert, block_m, block_k, block_n,
                             interpret):
    return quant_ragged_block_sparse_matmul(
        x, tiles, counts, indices, slots, scales, tile_expert,
        block_m=block_m, block_k=block_k, block_n=block_n,
        interpret=interpret)


def quant_ragged_blocksparse_matmul(x, tiles, counts, indices, slots,
                                    scales, tile_expert,
                                    block_m=RAGGED_BLOCK_ROWS, block_k=128,
                                    block_n=128, interpret=None):
    """Public op: the ragged routed-tokens-only launch with kept tiles
    stored int8 + pow2 scales."""
    counters.record("grouped_block_sparse_ragged_quant")
    E = counts.shape[0]
    live = tile_expert >= 0
    occupied = (jnp.zeros((E,), jnp.int32)
                .at[jnp.maximum(tile_expert, 0)]
                .max(live.astype(jnp.int32)).sum())
    counters.record_concrete(
        "grouped_block_sparse_ragged_quant_experts_computed", occupied)
    return _quant_ragged_matmul_jit(x, tiles, counts, indices, slots,
                                    scales, tile_expert.astype(jnp.int32),
                                    block_m, block_k, block_n,
                                    interpret_mode(interpret))


def ragged_blocksparse_matmul(x, w, counts, indices, tile_expert,
                              block_m=RAGGED_BLOCK_ROWS, block_k=128,
                              block_n=128, interpret=None):
    """Public op: the ragged expert batch (routed tokens packed into
    ``block_m``-aligned per-expert segments) through every owning
    expert's tile plan, one launch, M-grid sized by the packed buffer
    rather than E·capacity. ``tile_expert`` maps each M-tile to its
    expert (``-1`` = dead padding tile, skipped)."""
    counters.record("grouped_block_sparse_ragged")
    E = w.shape[0]
    live = tile_expert >= 0
    occupied = (jnp.zeros((E,), jnp.int32)
                .at[jnp.maximum(tile_expert, 0)]
                .max(live.astype(jnp.int32)).sum())
    counters.record_concrete("grouped_block_sparse_ragged_experts_computed",
                             occupied)
    return _ragged_matmul_jit(x, w, counts, indices,
                              tile_expert.astype(jnp.int32), block_m,
                              block_k, block_n, interpret_mode(interpret))
