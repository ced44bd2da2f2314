"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_sparse.ops import (block_mask_from_weight_mask,
                                            blocksparse_matmul, plan_blocks)
from repro.kernels.block_sparse.ref import block_sparse_matmul_ref
from repro.kernels.flash_attention.ops import flash_attention_bshd
from repro.kernels import counters
from repro.kernels.grouped_block_sparse.ops import (
    RAGGED_BLOCK_ROWS, grouped_blocksparse_matmul,
    ragged_blocksparse_matmul, stack_expert_plans)
from repro.kernels.grouped_block_sparse.ref import (
    grouped_block_sparse_matmul_ref, ragged_block_sparse_matmul_ref)
from repro.kernels.paged_attention.ops import paged_attention_decode
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_apply
from repro.kernels.wanda_metric.ops import outlier_ratio as kernel_outlier
from repro.kernels.wanda_metric.ref import outlier_ratio_ref
from repro.models.layers import _dense_attention, paged_gather
from repro.models.ssm import ssd_chunked

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [(256, 512, 384), (128, 256, 128),
                                 (384, 384, 256)])
def test_block_sparse_matmul(dtype, mkn):
    M, K, N = mkn
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (M, K)).astype(dtype)
    w = jax.random.normal(ks[1], (K, N)).astype(dtype)
    mask = np.array(jax.random.uniform(ks[2], (K, N)) > 0.7)
    mask[:128, :128] = False                       # force a zero block
    w = jnp.where(jnp.asarray(mask), w, 0).astype(dtype)
    bm = block_mask_from_weight_mask(mask, 128, 128)
    counts, idx = plan_blocks(bm)
    y = blocksparse_matmul(x, w, counts, idx, interpret=True)
    yref = block_sparse_matmul_ref(x, w, jnp.asarray(bm), 128, 128)
    err = jnp.abs(y.astype(jnp.float32) - yref.astype(jnp.float32)).max()
    scale = jnp.abs(yref.astype(jnp.float32)).max() + 1e-9
    assert float(err / scale) < TOL[dtype]


def test_block_sparse_skips_zero_blocks():
    mask = np.zeros((256, 256), bool)
    mask[:128, :128] = True
    bm = block_mask_from_weight_mask(mask, 128, 128)
    counts, idx = plan_blocks(bm)
    assert counts.tolist() == [1, 0]               # column 1 fully skipped
    x = jnp.ones((128, 256))
    w = jnp.ones((256, 256)) * jnp.asarray(mask)
    y = blocksparse_matmul(x, w, counts, idx, interpret=True)
    assert float(jnp.abs(y[:, 128:]).max()) == 0.0


def _expert_problem(E=4, M=96, K=64, N=80, block=16, keep=0.4, seed=0):
    """Random per-expert weights with diverging tile densities + the
    stacked grouped plan built from independent per-expert plans."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(E, M, K)).astype(np.float32))
    w = rng.normal(size=(E, K, N)).astype(np.float32)
    masks = np.zeros((E, K, N), bool)
    for e in range(E):
        # tile-level masks so pruned tiles are exactly skippable tiles;
        # density rises with e => per-expert max_nnz diverges
        bm = rng.random((K // block, N // block)) < keep + 0.15 * e
        bm[0, 0] = True                     # never a fully empty plan
        masks[e] = np.repeat(np.repeat(bm, block, 0), block, 1)
    w = np.where(masks, w, 0.0)
    counts_e, indices_e, bms = [], [], []
    for e in range(E):
        bm = block_mask_from_weight_mask(masks[e], block, block)
        c, i = plan_blocks(bm)
        counts_e.append(c)
        indices_e.append(i)
        bms.append(bm)
    counts, indices = stack_expert_plans(counts_e, indices_e)
    return (x, jnp.asarray(w), jnp.asarray(counts), jnp.asarray(indices),
            counts_e, indices_e, jnp.asarray(np.stack(bms)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_block_sparse_vs_ref(dtype):
    B = 16
    x, w, counts, indices, _, _, bms = _expert_problem()
    y = grouped_blocksparse_matmul(x.astype(dtype), w.astype(dtype),
                                   counts, indices, block_k=B, block_n=B,
                                   interpret=True)
    yref = grouped_block_sparse_matmul_ref(x.astype(dtype), w.astype(dtype),
                                           bms, B, B)
    err = jnp.abs(y.astype(jnp.float32) - yref.astype(jnp.float32)).max()
    scale = jnp.abs(yref.astype(jnp.float32)).max() + 1e-9
    assert float(err / scale) < TOL[dtype]


@pytest.mark.parametrize("block_m", [None, 16, 48])
def test_grouped_matches_per_expert_launches(block_m):
    """One grouped launch == E per-expert block_sparse launches,
    bitwise (same f32 accumulation order per expert), for both the
    resident-panel default and explicit M tiling."""
    B = 16
    x, w, counts, indices, counts_e, indices_e, _ = _expert_problem()
    y = grouped_blocksparse_matmul(x, w, counts, indices, block_m=block_m,
                                   block_k=B, block_n=B, interpret=True)
    for e in range(x.shape[0]):
        ye = blocksparse_matmul(x[e], w[e], counts[e], indices[e],
                                block_m=16, block_k=B, block_n=B,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(y[e]), np.asarray(ye))
        # and vs each expert's own (unpadded-max_nnz) solo plan
        solo = blocksparse_matmul(x[e], w[e], counts_e[e], indices_e[e],
                                  block_m=16, block_k=B, block_n=B,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(y[e]), np.asarray(solo),
                                   rtol=0, atol=2e-5)


def test_grouped_skips_fully_pruned_expert_column():
    """count==0 block-columns produce exact zeros, per expert — a column
    dense in expert 1 can be fully skipped in expert 0."""
    B, E, K, N = 16, 2, 32, 32
    masks = np.zeros((E, K, N), bool)
    masks[0, :, :16] = True                 # expert 0: column 1 empty
    masks[1, :, :] = True                   # expert 1: fully dense
    w = np.where(masks, 1.0, 0.0).astype(np.float32)
    counts_e, indices_e = zip(*(plan_blocks(
        block_mask_from_weight_mask(masks[e], B, B)) for e in range(E)))
    counts, indices = stack_expert_plans(counts_e, indices_e)
    x = jnp.ones((E, 16, K), jnp.float32)
    y = grouped_blocksparse_matmul(x, jnp.asarray(w), jnp.asarray(counts),
                                   jnp.asarray(indices), block_k=B,
                                   block_n=B, interpret=True)
    assert float(jnp.abs(y[0, :, 16:]).max()) == 0.0
    assert float(jnp.abs(y[1]).min()) > 0.0


# ------------------------------------------- occupancy-aware dispatch

def _occupancy_rows(pattern, E, M, rng):
    """Per-expert live-row counts for an occupancy pattern."""
    if pattern == "all-empty":
        return np.zeros(E, np.int64)
    if pattern == "one-hot":
        rows = np.zeros(E, np.int64)
        rows[rng.integers(E)] = max(1, M // 3)
        return rows
    if pattern == "skewed":
        rows = np.zeros(E, np.int64)
        rows[0] = M
        for e in range(1, E):
            rows[e] = max(0, 3 - e)
        return rows
    if pattern == "full":
        return np.full(E, M, np.int64)
    return rng.integers(0, M + 1, E)          # randomized fuzz


OCCUPANCY_PATTERNS = ["all-empty", "one-hot", "skewed", "full",
                      "random-0", "random-1", "random-2"]


@pytest.mark.parametrize("block_m", [None, 16])
@pytest.mark.parametrize("pattern", OCCUPANCY_PATTERNS)
def test_grouped_masked_occupancy_fuzz(pattern, block_m):
    """The occupancy-masked grouped launch: live rows bitwise-match the
    unmasked launch, fully-dead experts produce exact zeros, and the
    counters pin that empty experts contribute no computed-expert work."""
    B = 16
    x, w, counts, indices, _, _, _ = _expert_problem()
    E, M, _ = x.shape
    rng = np.random.default_rng(abs(hash(pattern)) % 2**32)
    rows = _occupancy_rows(pattern, E, M, rng)
    row_live = jnp.asarray(np.arange(M)[None, :] < rows[:, None])
    counters.reset()
    y = grouped_blocksparse_matmul(x, w, counts, indices, block_m=block_m,
                                   block_k=B, block_n=B, interpret=True,
                                   row_live=row_live)
    snap = counters.snapshot()
    occ = int((rows > 0).sum())
    assert snap["grouped_block_sparse"] == 1
    assert snap.get("grouped_block_sparse_experts_computed", 0) == occ
    y_full = grouped_blocksparse_matmul(x, w, counts, indices,
                                        block_m=block_m, block_k=B,
                                        block_n=B, interpret=True)
    for e in range(E):
        np.testing.assert_array_equal(np.asarray(y[e, :rows[e]]),
                                      np.asarray(y_full[e, :rows[e]]))
    if (rows == 0).any():
        dead = np.asarray(y)[rows == 0]
        assert float(np.abs(dead).max()) == 0.0


@pytest.mark.parametrize("pattern", OCCUPANCY_PATTERNS)
def test_ragged_occupancy_fuzz(pattern):
    """The ragged kernel over packed per-expert segments: each occupied
    segment bitwise-matches that expert's own block_sparse launch, dead
    padding tiles are exact zeros, and the counters pin that experts
    with zero routed tokens launch zero tile work."""
    B = 16
    _, w, counts, indices, _, _, bms = _expert_problem()
    E, K, _ = w.shape
    A = RAGGED_BLOCK_ROWS
    rng = np.random.default_rng(abs(hash(pattern)) % 2**32)
    rows = _occupancy_rows(pattern, E, 48, rng)
    rows = np.minimum(rows, 48)
    seg = -(-rows // A) * A
    ends = np.cumsum(seg)
    off = ends - seg
    m_max = int(max(ends[-1], A)) + A          # leave >=1 dead tail tile
    tile_expert = np.full(m_max // A, -1, np.int32)
    for e in range(E):
        tile_expert[off[e] // A: ends[e] // A] = e
    x = np.zeros((m_max, K), np.float32)
    for e in range(E):
        x[off[e]:off[e] + rows[e]] = rng.normal(size=(rows[e], K))
    counters.reset()
    y = ragged_blocksparse_matmul(jnp.asarray(x), w, counts, indices,
                                  jnp.asarray(tile_expert), block_k=B,
                                  block_n=B, interpret=True)
    snap = counters.snapshot()
    occ = int((rows > 0).sum())
    assert snap["grouped_block_sparse_ragged"] == 1
    assert snap.get("grouped_block_sparse_ragged_experts_computed", 0) == occ
    # dead tiles: exact zeros
    dead = np.asarray(y).reshape(m_max // A, A, -1)[tile_expert < 0]
    assert dead.size and float(np.abs(dead).max()) == 0.0
    # vs the pure-jnp oracle
    yref = ragged_block_sparse_matmul_ref(jnp.asarray(x), w,
                                          tile_expert, A, bms, B, B)
    scale = float(jnp.abs(yref).max()) + 1e-9
    assert float(jnp.abs(y - yref).max() / scale) < TOL[jnp.float32]
    # each occupied segment == that expert's solo block_sparse launch,
    # bitwise (same tile height, same f32 accumulation order)
    for e in range(E):
        if rows[e] == 0:
            continue
        ye = blocksparse_matmul(jnp.asarray(x[off[e]:ends[e]]), w[e],
                                counts[e], indices[e], block_m=A,
                                block_k=B, block_n=B, interpret=True)
        np.testing.assert_array_equal(np.asarray(y[off[e]:ends[e]]),
                                      np.asarray(ye))


@pytest.mark.parametrize("shape", [(512, 768), (256, 256), (1024, 512)])
def test_wanda_outlier_kernel(shape):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    K, N = shape
    spikes = (jax.random.uniform(k2, shape) > 0.995).astype(jnp.float32)
    w = jax.random.normal(k1, shape) * (1 + 20 * spikes)
    a = jnp.abs(jax.random.normal(k2, (K,))) + 0.1
    r_k = float(kernel_outlier(w, a, alpha=5.0, interpret=True))
    r_r = float(outlier_ratio_ref(w, a, 5.0))
    assert r_k == pytest.approx(r_r, abs=1e-4)


@pytest.mark.parametrize("dims", [(2, 64, 3, 16, 8, 16),
                                  (1, 128, 2, 32, 16, 32),
                                  (2, 96, 1, 16, 8, 32)])
def test_ssd_scan_kernel(dims):
    B, L, H, P, N, chunk = dims
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    xt = jax.random.normal(ks[0], (B, L, H, P))
    da = -jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.1
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    y_k = ssd_apply(xt, da, Bm, Cm, chunk=chunk, interpret=True)
    y_r, _ = ssd_chunked(xt, da, Bm, Cm, chunk)
    scale = float(jnp.abs(y_r).max()) + 1e-9
    assert float(jnp.abs(y_k - y_r).max() / scale) < 1e-5


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(hq, hkv, dtype):
    B, S, D = 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, hkv, D)).astype(dtype)
    o_k = flash_attention_bshd(q, k, v, block_q=128, block_k=128,
                               interpret=True)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o_r = _dense_attention(q, k, v, pos, pos, causal=True)
    err = jnp.abs(o_k.astype(jnp.float32) - o_r.astype(jnp.float32)).max()
    assert float(err) < (5e-6 if dtype == jnp.float32 else 3e-2)


# ------------------------------------------------------- paged attention

def _paged_case(hq, hkv, dtype, B=4, M=4, bs=8, D=16, seed=7):
    """Random paged decode problem: a shuffled arena (so physical order
    never matches logical order), ragged lengths, one query per row. The
    arenas are drawn ``(nb + 1, bs, hkv, D)`` and stored as the pool
    stores them, ``(nb + 1, bs, hkv * D)``."""
    rng = np.random.default_rng(seed)
    nb = B * M
    shape = (nb + 1, bs, hkv, D)
    k_arena = jnp.asarray(rng.normal(size=shape), dtype).reshape(
        nb + 1, bs, hkv * D)
    v_arena = jnp.asarray(rng.normal(size=shape), dtype).reshape(
        nb + 1, bs, hkv * D)
    tables = jnp.asarray(rng.permutation(nb).reshape(B, M), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, M * bs + 1, (B,)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, hq, D)), dtype)
    return q, k_arena, v_arena, tables, lengths


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel(hq, hkv, dtype):
    q, ka, va, tables, lengths = _paged_case(hq, hkv, dtype)
    o_k = paged_attention_decode(q, ka, va, tables, lengths, hkv,
                                 interpret=True)
    o_r = paged_attention_ref(q[:, 0].astype(jnp.float32),
                              ka.astype(jnp.float32),
                              va.astype(jnp.float32), tables, lengths,
                              hkv)
    err = jnp.abs(o_k[:, 0].astype(jnp.float32) - o_r).max()
    assert float(err) < (5e-6 if dtype == jnp.float32 else 3e-2)


def test_paged_attention_matches_gather_path():
    """The kernel must agree with the serving gather path itself
    (paged_gather + _dense_attention with the decode-time length mask),
    not just the standalone oracle."""
    hkv = 2
    q, ka, va, tables, lengths = _paged_case(4, hkv, jnp.float32, seed=11)
    o_k = paged_attention_decode(q, ka, va, tables, lengths, hkv,
                                 interpret=True)
    kview = paged_gather(ka, tables, hkv)
    vview = paged_gather(va, tables, hkv)
    T_kv = kview.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(T_kv, dtype=jnp.int32)[None, :],
                              (q.shape[0], T_kv))
    valid = kv_pos < lengths[:, None]
    # decode writes at position length-1, so causal == the length mask
    o_g = _dense_attention(q, kview, vview, (lengths - 1)[:, None],
                           kv_pos, causal=True, kv_valid=valid)
    assert float(jnp.abs(o_k - o_g).max()) < TOL[jnp.float32]


def test_paged_attention_scratch_masked_slot():
    """A slot mid-chunked-prefill rides the decode burst with its table
    masked to the scratch block and length clamped to 1 (its output is
    discarded): the kernel must stay finite for it and exact for the
    live rows."""
    hkv = 2
    q, ka, va, tables, lengths = _paged_case(4, hkv, jnp.float32, seed=13)
    scratch = ka.shape[0] - 1
    tables = tables.at[1].set(scratch)
    lengths = lengths.at[1].set(1)
    o_k = paged_attention_decode(q, ka, va, tables, lengths, hkv,
                                 interpret=True)
    assert bool(jnp.all(jnp.isfinite(o_k)))
    o_r = paged_attention_ref(q[:, 0], ka, va, tables, lengths, hkv)
    live = np.array([0, 2, 3])
    err = jnp.abs(o_k[live, 0] - o_r[live]).max()
    assert float(err) < TOL[jnp.float32]


def test_paged_attention_shared_prefix_tables():
    """Prefix sharing maps the same physical blocks into several rows'
    tables: rows with identical tables, lengths, and queries must
    produce identical outputs, and both must match the oracle."""
    hkv = 2
    q, ka, va, tables, lengths = _paged_case(4, hkv, jnp.float32, seed=17)
    tables = tables.at[2].set(tables[0])        # full shared view
    lengths = lengths.at[2].set(lengths[0])
    q = q.at[2].set(q[0])
    tables = tables.at[3, :2].set(tables[1, :2])  # shared 2-block prefix
    o_k = paged_attention_decode(q, ka, va, tables, lengths, hkv,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(o_k[2]), np.asarray(o_k[0]))
    o_r = paged_attention_ref(q[:, 0], ka, va, tables, lengths, hkv)
    assert float(jnp.abs(o_k[:, 0] - o_r).max()) < TOL[jnp.float32]
