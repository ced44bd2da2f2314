"""Ahead-of-time compiles of the main-path Pallas kernels at published
widths for a described TPU v5e chip (nothing runs; the TPU compiler is
asked whether it accepts each kernel, and the compiled program must
hold the kernel as a ``tpu_custom_call`` rather than interpreted HLO).

Widths: phi3-medium-14b (d 5120, d_ff 17920, 40 q / 10 kv heads, head
dim 128) and qwen3-moe-30b-a3b (d 2048, expert d_ff 768, 128 experts,
32 q / 4 kv heads). The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_sparse.kernel import (block_sparse_matmul,
                                               quant_block_sparse_matmul)
from repro.kernels.grouped_block_sparse.ops import (
    RAGGED_BLOCK_ROWS, grouped_blocksparse_matmul, ragged_blocksparse_matmul)
from repro.kernels.paged_attention.ops import paged_attention_decode
from repro.models.moe import ragged_rows_bound

B = 128                              # plan tile (recipe default)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("kn", [(5120, 17920), (17920, 5120)])
@pytest.mark.parametrize("m", [16, 128])
def test_block_sparse_compiles(one_chip, quant, kn, m):
    K, N = kn
    nN, max_nnz = N // B, K // B // 2          # half the tiles kept
    x = _spec((m, K), jnp.bfloat16, one_chip)
    counts = _spec((nN,), jnp.int32, one_chip)
    idx = _spec((nN, max_nnz), jnp.int32, one_chip)
    if quant == "none":
        w = _spec((K, N), jnp.bfloat16, one_chip)
        text = _compiled_text(
            lambda x, w, c, i: block_sparse_matmul(
                x, w, c, i, block_m=m, block_k=B, block_n=B),
            x, w, counts, idx)
    else:
        tiles = _spec((nN * max_nnz, B, B), jnp.int8, one_chip)
        slots = _spec((nN, max_nnz), jnp.int32, one_chip)
        scales = _spec((nN, max_nnz), jnp.float32, one_chip)
        text = _compiled_text(
            lambda x, t, c, i, sl, sc: quant_block_sparse_matmul(
                x, t, c, i, sl, sc, block_m=m, block_k=B, block_n=B),
            x, tiles, counts, idx, slots, scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads", [(40, 10), (32, 4)],
                         ids=["phi3-gqa4", "qwen3-gqa8"])
def test_paged_attention_compiles(one_chip, heads):
    n_q, n_kv = heads
    D, bs, slots, max_blocks = 128, 16, 8, 16
    n_rows = slots * max_blocks + 1            # arena + scratch block
    q = _spec((slots, 1, n_q, D), jnp.bfloat16, one_chip)
    arena = _spec((n_rows, bs, n_kv, D), jnp.bfloat16, one_chip)
    tables = _spec((slots, max_blocks), jnp.int32, one_chip)
    lengths = _spec((slots,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, k, v, t, n: paged_attention_decode(q, k, v, t, n,
                                                     interpret=False),
        q, arena, arena, tables, lengths)
    assert "tpu_custom_call" in text


# qwen3-moe expert widths: (K, N) of the up/gate and the down projection
MOE_DIRECTIONS = {"up": (2048, 768), "down": (768, 2048)}
N_EXPERTS = 128


@pytest.mark.parametrize("direction", ["up", "down"])
@pytest.mark.parametrize("m", [16, 256], ids=["decode", "prefill"])
def test_grouped_compiles_at_128_experts(one_chip, direction, m):
    K, N = MOE_DIRECTIONS[direction]
    E, nN, max_nnz = N_EXPERTS, N // B, K // B
    x = _spec((E, m, K), jnp.bfloat16, one_chip)
    w = _spec((E, K, N), jnp.bfloat16, one_chip)
    counts = _spec((E, nN), jnp.int32, one_chip)
    idx = _spec((E, nN, max_nnz), jnp.int32, one_chip)
    live = _spec((E, m), jnp.bool_, one_chip)
    text = _compiled_text(
        lambda x, w, c, i, r: grouped_blocksparse_matmul(
            x, w, c, i, block_k=B, block_n=B, interpret=False, row_live=r),
        x, w, counts, idx, live)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["up", "down"])
def test_ragged_compiles_at_128_experts(one_chip, direction):
    K, N = MOE_DIRECTIONS[direction]
    E, nN, max_nnz = N_EXPERTS, N // B, K // B
    M = ragged_rows_bound(E, 16 * 8)           # 16 decode tokens, top-8
    x = _spec((M, K), jnp.bfloat16, one_chip)
    w = _spec((E, K, N), jnp.bfloat16, one_chip)
    counts = _spec((E, nN), jnp.int32, one_chip)
    idx = _spec((E, nN, max_nnz), jnp.int32, one_chip)
    tile_expert = _spec((M // RAGGED_BLOCK_ROWS,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda x, w, c, i, te: ragged_blocksparse_matmul(
            x, w, c, i, te, block_k=B, block_n=B, interpret=False),
        x, w, counts, idx, tile_expert)
    assert "tpu_custom_call" in text
