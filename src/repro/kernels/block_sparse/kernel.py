"""Block-sparse GEMM Pallas TPU kernel — the TPU-native realisation of
composite-pruned projection matmuls (DESIGN.md §3.1).

Unstructured pruning at high POD targets leaves many all-zero 128x128
weight tiles. The kernel walks, per output block-column, a scalar-
prefetched list of the *nonzero* K-block indices only (MegaBlocks /
SplashAttention pattern): zero blocks cost neither HBM->VMEM traffic nor
MXU cycles. Grid = (M-blocks, N-blocks, max_nnz); padded steps are
masked out with @pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(count_ref, idx_ref, x_ref, w_ref, o_ref, acc_ref, *,
            max_nnz: int):
    n = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < count_ref[n])
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _quant_kernel(count_ref, idx_ref, slot_ref, scale_ref, x_ref, w_ref,
                  o_ref, acc_ref, *, max_nnz: int):
    n = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < count_ref[n])
    def _accum():
        # int8 magnitudes are exact in the compute dtype and the per-tile
        # scale is a power of two, so scaling the accumulated tile
        # product is bitwise-equal to pre-scaling the weight tile
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0].astype(x_ref.dtype),
                                preferred_element_type=jnp.float32
                                ) * scale_ref[n, s]

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_block_sparse_matmul(x: jax.Array, tiles: jax.Array,
                              counts: jax.Array, indices: jax.Array,
                              slots: jax.Array, scales: jax.Array, *,
                              block_m: int = 128, block_k: int = 128,
                              block_n: int = 128,
                              interpret: bool = False) -> jax.Array:
    """y = x @ w with the kept weight tiles stored as compacted int8.

    Same tile walk as :func:`block_sparse_matmul`, but instead of the
    dense (K, N) weight the kernel streams ``tiles`` — the plan's kept
    (block_k, block_n) tiles stacked in plan order as int8 — locating
    column ``n``'s step-``s`` tile via the scalar-prefetched
    ``slots (N/bn, max_nnz)`` map. ``scales (N/bn, max_nnz)`` holds the
    matching per-tile power-of-two dequant factors, applied once per
    tile to the accumulated product. Dead steps clamp their slot to the
    column's last kept tile so the revisit's DMA is elided.
    """
    M, K = x.shape
    assert tiles.shape[1:] == (block_k, block_n)
    N = counts.shape[0] * block_n
    assert M % block_m == 0 and K % block_k == 0
    max_nnz = indices.shape[1]

    grid = (M // block_m, N // block_n, max_nnz)
    kernel = functools.partial(_quant_kernel, max_nnz=max_nnz)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda m, n, s, cnt, idx, slt, scl:
                             (m, idx[n, s])),
                pl.BlockSpec((1, block_k, block_n),
                             lambda m, n, s, cnt, idx, slt, scl:
                             (slt[n, s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda m, n, s, cnt, idx, slt, scl:
                                   (m, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name="blocksparse_int8",
    )(counts, indices, slots, scales, x, tiles)


def block_sparse_matmul(x: jax.Array, w: jax.Array, counts: jax.Array,
                        indices: jax.Array, *, block_m: int = 128,
                        block_k: int = 128, block_n: int = 128,
                        interpret: bool = False) -> jax.Array:
    """y = x @ w, visiting only nonzero (K-block, N-block) weight tiles.

    x: (M, K); w: (K, N) (zeros in pruned blocks);
    counts: (N/bn,) int32 — nonzero K-blocks per output block-column;
    indices: (N/bn, max_nnz) int32 — their K-block ids (padded by repeating
    the last valid id so prefetch stays in-bounds).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    max_nnz = indices.shape[1]

    grid = (M // block_m, N // block_n, max_nnz)
    kernel = functools.partial(_kernel, max_nnz=max_nnz)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda m, n, s, cnt, idx: (m, idx[n, s])),
                pl.BlockSpec((block_k, block_n),
                             lambda m, n, s, cnt, idx: (idx[n, s], n)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda m, n, s, cnt, idx: (m, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        # not "block_sparse": bench/devtrace.py finds that text anywhere
        # in an op, operands included, so every consumer of the result
        # (the LM head among them) would count as this kernel's time
        name="blocksparse",
    )(counts, indices, x, w)
