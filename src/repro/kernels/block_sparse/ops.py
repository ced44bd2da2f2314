"""Jit-ready wrappers: block-map construction from unstructured-pruning
masks + the dispatch into the Pallas kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import counters, interpret_mode
from repro.kernels.block_sparse.kernel import (block_sparse_matmul,
                                               quant_block_sparse_matmul)


def block_mask_from_weight_mask(mask, block_k: int, block_n: int):
    """Elementwise keep-mask (K, N) -> block-level nonzero map (K/bk, N/bn)."""
    K, N = mask.shape
    assert K % block_k == 0 and N % block_n == 0
    m = np.asarray(mask).reshape(K // block_k, block_k, N // block_n, block_n)
    return m.any(axis=(1, 3))


def plan_blocks(block_mask) -> tuple:
    """Block map -> (counts (nN,), indices (nN, max_nnz)) for the kernel."""
    bm = np.asarray(block_mask)
    nK, nN = bm.shape
    counts = bm.sum(axis=0).astype(np.int32)
    max_nnz = max(int(counts.max()), 1)
    indices = np.zeros((nN, max_nnz), np.int32)
    for n in range(nN):
        nz = np.nonzero(bm[:, n])[0]
        if len(nz) == 0:
            nz = np.array([0])
        pad = np.full(max_nnz - min(len(nz), max_nnz), nz[-1])
        indices[n] = np.concatenate([nz[:max_nnz], pad])
    return jnp.asarray(counts), jnp.asarray(indices)


def plan_slots(counts, max_nnz: int) -> tuple:
    """Compact-tile slot map for the quantized kernels.

    Kept tiles are stored in plan order — column n's tiles occupy
    consecutive storage rows — and ``slots[n, s]`` names the storage row
    of column ``n``'s step-``s`` tile. Steps past ``counts[n]`` clamp to
    the column's last kept tile (the revisit's DMA is elided), empty
    columns to row 0. Returns ``(slots (nN, max_nnz) int32, total)``
    where ``total`` is the kept-tile count (storage always holds
    ``max(total, 1)`` tiles)."""
    c = np.asarray(counts)
    off = np.concatenate([[0], np.cumsum(c)[:-1]]).astype(np.int64)
    total = int(c.sum())
    steps = np.minimum(np.arange(max_nnz)[None, :],
                       np.maximum(c - 1, 0)[:, None])
    slots = off[:, None] + steps
    return np.clip(slots, 0, max(total, 1) - 1).astype(np.int32), total


def gather_kept_tiles(w2, counts, indices, block_k: int,
                      block_n: int) -> np.ndarray:
    """The kept (block_k, block_n) tiles of a planned weight, stacked in
    plan order — the storage the quantized kernels stream instead of the
    dense weight. Returns (max(total, 1), block_k, block_n) float32 (a
    single zero tile when the plan keeps nothing)."""
    w2 = np.asarray(w2, np.float32)
    c = np.asarray(counts)
    idx = np.asarray(indices)
    tiles = []
    for n in range(c.shape[0]):
        for s in range(int(c[n])):
            k = int(idx[n, s])
            tiles.append(w2[k * block_k:(k + 1) * block_k,
                            n * block_n:(n + 1) * block_n])
    if not tiles:
        tiles = [np.zeros((block_k, block_n), np.float32)]
    return np.stack(tiles)


def sparse_density(block_mask) -> float:
    bm = np.asarray(block_mask)
    return float(bm.mean())


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _blocksparse_matmul_jit(x, w, counts, indices, block_m, block_k,
                            block_n, interpret):
    return block_sparse_matmul(x, w, counts, indices, block_m=block_m,
                               block_k=block_k, block_n=block_n,
                               interpret=interpret)


def blocksparse_matmul(x, w, counts, indices, block_m=128, block_k=128,
                       block_n=128, interpret=None):
    """Public op: y = x @ w visiting nonzero weight blocks only."""
    counters.record("block_sparse")
    return _blocksparse_matmul_jit(x, w, counts, indices, block_m, block_k,
                                   block_n, interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _quant_blocksparse_matmul_jit(x, tiles, counts, indices, slots, scales,
                                  block_m, block_k, block_n, interpret):
    return quant_block_sparse_matmul(x, tiles, counts, indices, slots,
                                     scales, block_m=block_m,
                                     block_k=block_k, block_n=block_n,
                                     interpret=interpret)


def quant_blocksparse_matmul(x, tiles, counts, indices, slots, scales,
                             block_m=128, block_k=128, block_n=128,
                             interpret=None):
    """Public op: y = x @ w with kept tiles stored int8 + pow2 scales."""
    counters.record("block_sparse_quant")
    return _quant_blocksparse_matmul_jit(x, tiles, counts, indices, slots,
                                         scales, block_m, block_k, block_n,
                                         interpret_mode(interpret))
