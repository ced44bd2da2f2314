"""Grouped block-sparse GEMM Pallas TPU kernels — all MoE experts' pruned
projection matmuls in ONE launch (MegaBlocks-style).

The per-expert serving path issues E separate ``block_sparse`` launches
per projection, serializing dispatch and leaving the MXU idle between
experts. Here the expert axis joins the grid instead: grid =
(E, M-blocks, N-blocks, max_nnz), and each program scalar-prefetches its
*own expert's* nonzero K-block indices from the stacked plan
(``counts (E, N/bn)``, ``indices (E, N/bn, max_nnz)``). Tile skips
compose across experts — a zero tile costs nothing no matter which
expert owns it — and the whole expert group pays one dispatch
round-trip. Experts share ``max_nnz`` (index rows are edge-padded by
``pack_expert_projection``; padded steps are masked on ``counts``), so
a denser expert never starves a sparser one of grid steps it needs.

Unlike the dense-weight kernel, ``block_m`` here usually covers the
*whole* per-expert slot batch (the ops wrapper's panel default): each
expert's capacity-slot batch is small at decode time (C·G rows), so the
x panel stays resident while the grid walks that expert's nonzero
(K-block, N-block) tiles — each weight tile is then touched exactly
once per launch instead of once per M-block, the MegaBlocks layout.

Two occupancy-aware refinements on top (router counts prefetched
alongside the tile plan):

* **Masked grid** (:func:`grouped_block_sparse_matmul` with a ``work``
  array): a third scalar-prefetch arg ``work (E, M/bm)`` marks which
  per-expert M-blocks hold any routed token. Dead (expert, M-block)
  pairs skip the MXU entirely and clamp their x/w index maps to the
  step-0 block — consecutive grid steps then revisit the same block and
  the DMA is elided, exactly paged_attention's dead-block idiom. Output
  blocks are still flushed (zeros), so results are bitwise-identical to
  the unmasked launch on every row routing later gathers.

* **Ragged grid** (:func:`ragged_block_sparse_matmul`): the E axis
  leaves the grid entirely. Routed tokens are packed into one
  contiguous ``(M, K)`` buffer of ``block_m``-aligned per-expert
  segments, and a prefetched ``tile_expert (M/bm,)`` map (from the
  cumsum of router counts; ``-1`` = past-the-end padding) tells each
  M-tile which expert's plan and weights it runs. The grid is
  (M/bm, N-blocks, max_nnz) — proportional to tokens actually routed,
  not E·capacity.

Every scalar-prefetched table is flattened to 1-D before the launch.
SMEM pads the last dimension of a multi-dimensional array to 128
words, so an ``(E, N/bn, max_nnz)`` index table with ``max_nnz`` 6
costs as much as one with 128: at 128 experts that overflows the 1 MiB
of SMEM. Flat, the tables cost their true size; the kernels address
them with the row-major offsets below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flat(*arrays):
    return tuple(a.reshape(-1) for a in arrays)


def _kernel(count_ref, idx_ref, work_ref, x_ref, w_ref, o_ref, acc_ref, *,
            max_nnz: int, n_n: int, n_m: int):
    e = pl.program_id(0)
    m = pl.program_id(1)
    n = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((s < count_ref[e * n_n + n]) & (work_ref[e * n_m + m] > 0))
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_block_sparse_matmul(x: jax.Array, w: jax.Array,
                                counts: jax.Array, indices: jax.Array, *,
                                work: jax.Array | None = None,
                                block_m: int = 128, block_k: int = 128,
                                block_n: int = 128,
                                interpret: bool = False) -> jax.Array:
    """y[e] = x[e] @ w[e] for every expert e, one kernel launch total,
    visiting only each expert's nonzero (K-block, N-block) weight tiles.

    x: (E, M, K) — per-expert capacity-slot batches;
    w: (E, K, N) — expert weight stack (zeros in pruned blocks);
    counts: (E, N/bn) int32 — nonzero K-blocks per expert/block-column;
    indices: (E, N/bn, max_nnz) int32 — their K-block ids (edge-padded to
    the shared max_nnz so the stack is rectangular);
    work: optional (E, M/bm) int32 — occupancy per (expert, M-block);
    zero entries skip compute and elide DMAs (their output blocks flush
    as zeros). None computes every block (all-occupied).
    """
    E, M, K = x.shape
    E2, K2, N = w.shape
    assert E == E2 and K == K2
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    assert counts.shape == (E, N // block_n)
    max_nnz = indices.shape[-1]
    if work is None:
        work = jnp.ones((E, M // block_m), jnp.int32)
    assert work.shape == (E, M // block_m)

    n_n, n_m = N // block_n, M // block_m

    def k_block(e, m, n, s, idx, wrk):
        # dead (e, m)-blocks pin the K-block to the step-0 one so every
        # later step revisits it and the DMA is elided
        row = (e * n_n + n) * max_nnz
        return jnp.where(wrk[e * n_m + m] > 0, idx[row + s], idx[row])

    def x_map(e, m, n, s, cnt, idx, wrk):
        return (e, m, k_block(e, m, n, s, idx, wrk))

    def w_map(e, m, n, s, cnt, idx, wrk):
        return (e, k_block(e, m, n, s, idx, wrk), n)

    grid = (E, n_m, n_n, max_nnz)
    kernel = functools.partial(_kernel, max_nnz=max_nnz, n_n=n_n, n_m=n_m)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_m, block_k), x_map),
                pl.BlockSpec((1, block_k, block_n), w_map),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda e, m, n, s, cnt, idx, wrk:
                                   (e, m, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, N), x.dtype),
        interpret=interpret,
    )(*_flat(counts, indices, work), x, w)


def _quant_kernel(count_ref, idx_ref, slot_ref, scale_ref, work_ref, x_ref,
                  w_ref, o_ref, acc_ref, *, max_nnz: int, n_n: int,
                  n_m: int):
    e = pl.program_id(0)
    m = pl.program_id(1)
    n = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((s < count_ref[e * n_n + n]) & (work_ref[e * n_m + m] > 0))
    def _accum():
        # pow2 per-tile scale on the accumulated product: bitwise-equal
        # to the unquantized kernel over the fake-quant weight stack
        acc_ref[...] += jnp.dot(x_ref[0], w_ref[0].astype(x_ref.dtype),
                                preferred_element_type=jnp.float32
                                ) * scale_ref[(e * n_n + n) * max_nnz + s]

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def quant_grouped_block_sparse_matmul(x: jax.Array, tiles: jax.Array,
                                      counts: jax.Array,
                                      indices: jax.Array,
                                      slots: jax.Array,
                                      scales: jax.Array, *,
                                      work: jax.Array | None = None,
                                      block_m: int = 128,
                                      block_k: int = 128,
                                      block_n: int = 128,
                                      interpret: bool = False) -> jax.Array:
    """The grouped launch over int8 kept-tile storage.

    Same grid and occupancy masking as
    :func:`grouped_block_sparse_matmul`, but the dense ``(E, K, N)``
    weight stack is replaced by ``tiles`` — every expert's kept tiles
    concatenated in plan order into one ``(T, block_k, block_n)`` int8
    array — with ``slots (E, N/bn, max_nnz)`` holding *absolute* storage
    rows and ``scales (E, N/bn, max_nnz)`` the per-tile pow2 dequant
    factors, both scalar-prefetched beside the plan.
    """
    E, M, K = x.shape
    assert tiles.shape[1:] == (block_k, block_n)
    N = counts.shape[1] * block_n
    assert M % block_m == 0 and K % block_k == 0
    max_nnz = indices.shape[-1]
    if work is None:
        work = jnp.ones((E, M // block_m), jnp.int32)
    assert work.shape == (E, M // block_m)

    n_n, n_m = N // block_n, M // block_m

    def step(table, e, m, n, s, wrk):
        row = (e * n_n + n) * max_nnz
        return jnp.where(wrk[e * n_m + m] > 0, table[row + s], table[row])

    def x_map(e, m, n, s, cnt, idx, slt, scl, wrk):
        return (e, m, step(idx, e, m, n, s, wrk))

    def w_map(e, m, n, s, cnt, idx, slt, scl, wrk):
        return (step(slt, e, m, n, s, wrk), 0, 0)

    grid = (E, n_m, n_n, max_nnz)
    kernel = functools.partial(_quant_kernel, max_nnz=max_nnz, n_n=n_n,
                               n_m=n_m)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_m, block_k), x_map),
                pl.BlockSpec((1, block_k, block_n), w_map),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda e, m, n, s, cnt, idx, slt, scl,
                                   wrk: (e, m, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, N), x.dtype),
        interpret=interpret,
    )(*_flat(counts, indices, slots, scales, work), x, tiles)


def _quant_ragged_kernel(count_ref, idx_ref, slot_ref, scale_ref, tile_ref,
                         x_ref, w_ref, o_ref, acc_ref, *, max_nnz: int,
                         n_n: int):
    t = pl.program_id(0)
    n = pl.program_id(1)
    s = pl.program_id(2)
    e = tile_ref[t]
    ec = jnp.maximum(e, 0)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((e >= 0) & (s < count_ref[ec * n_n + n]))
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0].astype(x_ref.dtype),
                                preferred_element_type=jnp.float32
                                ) * scale_ref[(ec * n_n + n) * max_nnz + s]

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_ragged_block_sparse_matmul(x: jax.Array, tiles: jax.Array,
                                     counts: jax.Array, indices: jax.Array,
                                     slots: jax.Array, scales: jax.Array,
                                     tile_expert: jax.Array, *,
                                     block_m: int = 16, block_k: int = 128,
                                     block_n: int = 128,
                                     interpret: bool = False) -> jax.Array:
    """The ragged routed-tokens-only launch over int8 kept-tile storage
    (``slots``/``scales`` as in :func:`quant_grouped_block_sparse_matmul`;
    dead tiles clamp their slot like they clamp their K-block index)."""
    M, K = x.shape
    assert tiles.shape[1:] == (block_k, block_n)
    E, nN = counts.shape
    N = nN * block_n
    assert M % block_m == 0 and K % block_k == 0
    assert tile_expert.shape == (M // block_m,)
    max_nnz = indices.shape[-1]

    def step(table, t, n, s, te):
        row = (jnp.maximum(te[t], 0) * nN + n) * max_nnz
        return jnp.where(te[t] >= 0, table[row + s], table[row])

    def x_map(t, n, s, cnt, idx, slt, scl, te):
        return (t, step(idx, t, n, s, te))

    def w_map(t, n, s, cnt, idx, slt, scl, te):
        return (step(slt, t, n, s, te), 0, 0)

    grid = (M // block_m, nN, max_nnz)
    kernel = functools.partial(_quant_ragged_kernel, max_nnz=max_nnz,
                               n_n=nN)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), x_map),
                pl.BlockSpec((1, block_k, block_n), w_map),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda t, n, s, cnt, idx, slt, scl, te:
                                   (t, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(*_flat(counts, indices, slots, scales), tile_expert, x, tiles)


def _ragged_kernel(count_ref, idx_ref, tile_ref, x_ref, w_ref, o_ref,
                   acc_ref, *, max_nnz: int, n_n: int):
    t = pl.program_id(0)
    n = pl.program_id(1)
    s = pl.program_id(2)
    e = tile_ref[t]
    ec = jnp.maximum(e, 0)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((e >= 0) & (s < count_ref[ec * n_n + n]))
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ragged_block_sparse_matmul(x: jax.Array, w: jax.Array,
                               counts: jax.Array, indices: jax.Array,
                               tile_expert: jax.Array, *,
                               block_m: int = 16, block_k: int = 128,
                               block_n: int = 128,
                               interpret: bool = False) -> jax.Array:
    """y = x @ w[tile_expert] over a ragged expert-packed batch, one
    launch, grid proportional to routed tokens instead of E·capacity.

    x: (M, K) — routed tokens packed into ``block_m``-aligned per-expert
    segments (the MegaBlocks layout; rows past an expert's count are
    zero padding inside its last tile);
    w: (E, K, N) — expert weight stack;
    counts / indices: the stacked tile plan (as in
    :func:`grouped_block_sparse_matmul`);
    tile_expert: (M/bm,) int32 — which expert owns each M-tile, ``-1``
    for dead tiles past the packed total (skipped: no MXU work, index
    maps clamped so their DMAs are elided, output flushed as zeros).
    """
    M, K = x.shape
    E, K2, N = w.shape
    assert K == K2
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    assert counts.shape == (E, N // block_n)
    assert tile_expert.shape == (M // block_m,)
    max_nnz = indices.shape[-1]

    n_n = N // block_n

    def k_block(t, n, s, idx, te):
        row = (jnp.maximum(te[t], 0) * n_n + n) * max_nnz
        return jnp.where(te[t] >= 0, idx[row + s], idx[row])

    def x_map(t, n, s, cnt, idx, te):
        return (t, k_block(t, n, s, idx, te))

    def w_map(t, n, s, cnt, idx, te):
        return (jnp.maximum(te[t], 0), k_block(t, n, s, idx, te), n)

    grid = (M // block_m, n_n, max_nnz)
    kernel = functools.partial(_ragged_kernel, max_nnz=max_nnz, n_n=n_n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), x_map),
                pl.BlockSpec((1, block_k, block_n), w_map),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda t, n, s, cnt, idx, te: (t, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(*_flat(counts, indices), tile_expert, x, w)
