"""Kernel benchmarks (structural, CPU container).

interpret-mode timings do not reflect TPU performance, so for each kernel
we report (a) allclose-vs-oracle error and (b) the *derived* TPU win:
block-sparse — fraction of weight tiles skipped (= MXU/HBM work saved);
flash attention — score-matrix HBM traffic avoided; ssd_scan — state
HBM round-trips avoided vs a naive scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import get_trained_model, rank_artifact, time_call
from repro.core.prune_controller import run_pruning_controller
from repro.core.registry import projections
from repro.common.tree import tree_get
from repro.kernels.block_sparse.ops import (block_mask_from_weight_mask,
                                            blocksparse_matmul, plan_blocks)
from repro.kernels.block_sparse.ref import block_sparse_matmul_ref


def bench_block_sparse(p: float = 0.8, block: int = 16):
    """Block-skip fraction on a real Mosaic-pruned model + allclose.

    Uses the TPU-native block-structured mask mode (wanda_block): pruned
    tiles are exactly what the Pallas kernel skips — skip_frac ~ p."""
    cfg, params, c = get_trained_model()
    art = rank_artifact(params, cfg, c)
    res = run_pruning_controller(params, cfg, art, p,
                                 category="unstructured",
                                 selector="wanda_block")
    skipped, total = 0, 0
    for proj in projections(res.cfg):
        w = np.asarray(tree_get(res.params, proj.path))
        w2 = w.reshape(-1, w.shape[-1])
        K, N = (w2.shape[0] // block) * block, (w2.shape[1] // block) * block
        if K == 0 or N == 0:
            continue
        bm = block_mask_from_weight_mask(w2[:K, :N] != 0, block, block)
        skipped += int((~bm).sum())
        total += bm.size
    # correctness at kernel block size on a synthetic case
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 512))
    w = jax.random.normal(key, (512, 384))
    mask = np.array(jax.random.uniform(key, (512, 384)) > 0.85)
    w = jnp.where(jnp.asarray(mask), w, 0)
    bm = block_mask_from_weight_mask(mask, 128, 128)
    counts, idx = plan_blocks(bm)
    y = blocksparse_matmul(x, w, counts, idx)
    yref = block_sparse_matmul_ref(x, w, jnp.asarray(bm), 128, 128)
    err = float(jnp.abs(y - yref).max())
    return {"skip_frac": skipped / max(total, 1), "allclose_err": err,
            "p": p, "block": block}


def bench_quant_sparse(block: int = 16, K: int = 256, N: int = 256):
    """Kept-tile int8 path vs its dequantized reference.

    Packs a block-structured synthetic weight with ``quant="int8"``,
    then checks (a) the quantized kernel is *bitwise* identical to the
    unquantized kernel over the fake-quant weight (pow2 scales commute
    with float rounding), (b) the real int8 storage — tiles + scale map
    + plan — versus a dense bf16 copy, (c) quantization error against
    the unquantized dense product."""
    from repro.serve.sparse import (dequantized_weight, pack_projection,
                                    sparse_linear)
    kx, kw, km = jax.random.split(jax.random.PRNGKey(3), 3)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    keep = jax.random.uniform(km, (K // block, N // block)) > 0.8
    mask = jnp.repeat(jnp.repeat(keep, block, 0), block, 1)
    w = jnp.where(mask, w, 0)
    p = pack_projection(w, block, quant="int8")
    wfq = jnp.asarray(dequantized_weight(p, K))
    x = jax.random.normal(kx, (64, K), jnp.float32)
    y_q = sparse_linear(x, wfq, p, quant="int8")
    y_ref = sparse_linear(x, wfq, p, quant="none")
    dense = x @ w
    tile_bytes = int(p.tiles.size)                    # int8: 1 B/elem
    scale_bytes = int(p.scales.size) * 4
    plan_bytes = (int(p.counts.size) + int(p.indices.size)
                  + int(p.slots.size)) * 4
    bytes_int8 = tile_bytes + scale_bytes + plan_bytes
    return {"quant_identical": float(jnp.array_equal(y_q, y_ref)),
            "quant_bytes_ratio": bytes_int8 / (K * N * 2),
            "quant_rel_err": float(jnp.abs(y_q - dense).max()
                                   / jnp.abs(dense).max()),
            "quant_tile_bytes": tile_bytes,
            "quant_density": p.density}


def bench_attention_paths(S: int = 4096):
    """Chunked (flash-oracle) vs dense attention: CPU latency + the memory
    the flash path avoids (the S x S score matrix)."""
    from repro.models.layers import (_chunked_causal_attention,
                                     _dense_attention)
    B, H, D = 1, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    dense = jax.jit(lambda q, k, v: _dense_attention(q, k, v, pos, pos, True))
    chunk = jax.jit(lambda q, k, v: _chunked_causal_attention(q, k, v, pos))
    t_dense = time_call(dense, q, k, v, repeats=3)
    t_chunk = time_call(chunk, q, k, v, repeats=3)
    score_bytes = B * H * S * S * 4
    return {"dense_us": t_dense, "chunked_us": t_chunk,
            "score_matrix_mib_avoided": score_bytes / 2 ** 20}


def main(fast: bool = True):
    bs = bench_block_sparse()
    print(f"block_sparse,p={bs['p']},skip_frac={bs['skip_frac']:.3f},"
          f"err={bs['allclose_err']:.2e}")
    qs = bench_quant_sparse()
    bs.update(qs)          # quant metrics ride the block-sparse row
    print(f"quant_sparse,identical={bool(qs['quant_identical'])},"
          f"bytes_ratio={qs['quant_bytes_ratio']:.3f},"
          f"rel_err={qs['quant_rel_err']:.2e}")
    at = bench_attention_paths(2048 if fast else 4096)
    print(f"attention,dense_us={at['dense_us']:.0f},"
          f"chunked_us={at['chunked_us']:.0f},"
          f"score_MiB_avoided={at['score_matrix_mib_avoided']:.0f}")
    return bs, at


if __name__ == "__main__":
    main(fast=False)
