"""Ahead-of-time compiles of the main-path Pallas kernels at published
widths for a described TPU v5e chip (nothing runs; the TPU compiler is
asked whether it accepts each kernel, and the compiled program must
hold the kernel as a ``tpu_custom_call`` rather than interpreted HLO).

Widths: phi3-medium-14b (d 5120, d_ff 17920, 40 q / 10 kv heads, head
dim 128) and qwen3-moe-30b-a3b (d 2048, expert d_ff 768, 128 experts,
32 q / 4 kv heads). The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler."""
import functools
import math
import os
import re

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
RELAYOUT_OPS = {"copy", "copy-start", "reshape", "transpose"}


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
    arena = _spec((n_rows, bs, n_kv * D), jnp.bfloat16, one_chip)
    tables = _spec((slots, max_blocks), jnp.int32, one_chip)
    lengths = _spec((slots,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, k, v, t, n: paged_attention_decode(q, k, v, t, n, n_kv,
                                                     interpret=False),
        q, arena, arena, tables, lengths)
    assert "tpu_custom_call" in text


def _entry_ops(text: str):
    """(opcode, largest result element count) of each instruction of
    the compiled program's ENTRY computation."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z][\w-]*)\(", line)
        if not m:
            continue
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
        yield m.group(2), max(sizes, default=0)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_step_updates_the_arena_in_place(one_chip, monkeypatch, step):
    """The engine's paged decode-and-sample step and its paged prefill
    step (a 256-token chunk) at phi3-medium widths (2 layers, 64 slots,
    an 801-block arena of 128-token blocks, the fused decode kernel),
    built as on the chip: the backend reads as the TPU, so the kernel
    compiles and the pool is donated. Each K and V arena is then
    scattered into in place: no copy, reshape or transpose of an arena's
    size is left in the program, and the pool is aliased from input to
    output in full."""
    from repro.configs.phi3_medium_14b import config
    from repro.models import transformer as T
    from repro.serve.batching import ContinuousEngine
    from repro.serve.config import ServeConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = config().replace(n_periods=2, scan_layers=False)
    serve = ServeConfig(max_slots=64, max_seq=4096, block_size=128,
                        n_blocks=800, paged_kernel=True, prefill_chunk=256)
    on_chip = functools.partial(jax.tree.map, lambda t: _spec(
        t.shape, t.dtype, one_chip))
    params = on_chip(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(lambda: T.init_paged_pool(
        cfg, serve.arena_blocks, serve.block_size, jnp.bfloat16)))
    eng = ContinuousEngine(params, cfg, serve)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    n, m = serve.max_slots, serve.blocks_per_seq
    if step == "decode":
        lowered = eng._decode_sample.lower(
            params, pool, i32((n, 1)), i32((n,)),
            _spec((n, 2), jnp.uint32, one_chip), i32((n,)),
            _spec((n,), jnp.float32, one_chip), i32((n, m)))
    else:
        lowered = eng._prefill.lower(
            params, pool, i32((1, serve.prefill_chunk)), i32((1, m)),
            i32((1,)), i32((1,)))
    compiled = lowered.compile()
    text = compiled.as_text()
    if step == "decode":
        assert "tpu_custom_call" in text
    arena = math.prod(pool[0]["attn"]["k"].shape)
    assert arena == 801 * 128 * 10 * 128
    relayouts = [(op, size) for op, size in _entry_ops(text)
                 if size >= arena and op in RELAYOUT_OPS]
    assert relayouts == []
    pool_bytes = sum(t.size * t.dtype.itemsize
                     for t in jax.tree.leaves(pool))
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


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
