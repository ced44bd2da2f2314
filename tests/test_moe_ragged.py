"""Ragged (MegaBlocks-style) MoE dispatch: property-test harness for the
packed-buffer layout, capacity clamping at tiny decode batches, path
equivalence (ragged == grouped == loop == dense token-for-token), and a
determinism pin — packing order must not change sampled tokens, and the
occupancy-dependent dispatch must not retrace across ticks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe
from repro.models.specs import MoESpec
from repro.serve.sparse import (RAGGED_TOKENS_MAX, pack_expert_projection,
                                plans_from_host, plans_to_host,
                                sparse_apply_moe)
from tests._hypothesis_compat import given, settings, st


def test_ragged_tile_height_matches_kernel():
    """The dispatch builder's segment alignment and the ragged kernel's
    M-tile height are the same contract; drift would misassign tiles."""
    from repro.kernels.grouped_block_sparse.ops import RAGGED_BLOCK_ROWS
    assert moe.RAGGED_BLOCK_ROWS == RAGGED_BLOCK_ROWS


# --------------------------------------------------- capacity regression

@pytest.mark.parametrize("E,top_k,cf,n_tokens", [
    (64, 1, 0.1, 1),      # cf*K*T/E = 0.0016 -> ceil must not hit 0
    (8, 1, 1.0, 1),
    (128, 2, 0.5, 2),
    (4, 2, 1.25, 1),
])
def test_capacity_never_zero_at_tiny_decode_batches(E, top_k, cf, n_tokens):
    spec = MoESpec(n_experts=E, top_k=top_k, d_ff=32, capacity_factor=cf)
    c = moe.capacity(spec, n_tokens)
    assert c >= 1
    # top_k experts per token are distinct, so per-expert demand at a
    # single-token decode tick is 1 — any positive capacity keeps it
    assert c >= top_k * n_tokens / E


def test_single_token_decode_drops_nothing():
    """A (1, 1) decode tick must route its token through all top_k
    experts even under extreme capacity pressure."""
    spec = MoESpec(n_experts=16, top_k=2, d_ff=32, capacity_factor=0.25)
    d = 32
    params = moe.init_moe(jax.random.PRNGKey(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, d), jnp.float32)
    y, _ = moe.apply_moe(params, spec, x)
    # a dropped assignment contributes 0; with all kept, the combine is a
    # convex mix of expert outputs and generically nonzero
    assert float(jnp.abs(y).max()) > 0.0


def test_capacity_dropped_assignments_contribute_zero():
    """Over-capacity assignments must combine as exact zeros. The old
    combine remapped drops to a -1 sentinel, and jnp.take's fill mode
    only catches indices >= n — so -1 WRAPPED to the last expert's last
    capacity slot and leaked that token's output into every drop."""
    E, d = 2, 32
    spec = MoESpec(n_experts=E, top_k=1, d_ff=32, capacity_factor=0.5)
    rng = np.random.default_rng(7)
    params = {
        # all-positive tokens x a one-sided router: every token routes
        # to expert 1 (the LAST expert, so its last capacity slot is
        # occupied — exactly the row the -1 wrap used to leak)
        "router": jnp.asarray(
            np.stack([np.zeros(d), np.full(d, 10.0)], axis=1), jnp.float32),
        "up": jnp.asarray(rng.normal(size=(E, d, 32)), jnp.float32),
        "gate": jnp.asarray(rng.normal(size=(E, d, 32)), jnp.float32),
        "down": jnp.asarray(rng.normal(size=(E, 32, d)), jnp.float32),
    }
    x = jnp.asarray(rng.uniform(0.1, 1.0, size=(1, 9, d)), jnp.float32)
    y, _ = moe.apply_moe(params, spec, x)
    # C = max(4, ...) = 4 here: tokens 0-3 keep their slot, 4-8 drop
    kept, dropped = np.asarray(y[0, :4]), np.asarray(y[0, 4:])
    assert float(np.abs(kept).min(axis=-1).max()) > 0.0
    assert float(np.abs(dropped).max()) == 0.0


# --------------------------------------- packed-buffer layout properties

def _routing(rng, E, top_k, cf, G, s):
    """Random router assignments shaped exactly like apply_moe's: top_k
    *distinct* experts per token, capacity keep/pos per (group, expert)."""
    spec = MoESpec(n_experts=E, top_k=top_k, d_ff=32, capacity_factor=cf)
    C = moe.capacity(spec, s)
    ids = np.stack([
        np.stack([rng.permutation(E)[:top_k] for _ in range(s)])
        for _ in range(G)])                                  # (G, s, K)
    flat_ids = jnp.asarray(ids.reshape(G, s * top_k))
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1
    keep = pos < C
    return spec, C, flat_ids, keep, pos


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=8),    # n_experts
       st.integers(min_value=1, max_value=4),    # top_k (clamped to E)
       st.floats(min_value=0.25, max_value=2.0),  # capacity_factor
       st.integers(min_value=1, max_value=4),    # groups (batch)
       st.integers(min_value=1, max_value=9),    # tokens per group
       st.integers(min_value=0, max_value=10**6))  # seed
def test_ragged_dispatch_layout_properties(E, top_k, cf, G, s, seed):
    top_k = min(top_k, E)
    rng = np.random.default_rng(seed)
    spec, C, flat_ids, keep, pos = _routing(rng, E, top_k, cf, G, s)
    A = moe.RAGGED_BLOCK_ROWS
    m_max = moe.ragged_rows_bound(E, G * s * top_k)
    dest, tile_expert, counts_e = moe.build_ragged_dispatch(
        flat_ids, keep, pos, E, m_max)
    dest, tile_expert, counts_e = (np.asarray(dest), np.asarray(tile_expert),
                                   np.asarray(counts_e))
    keep = np.asarray(keep)

    # per-expert counts account for every assignment minus capacity drops
    n_assign = G * s * top_k
    n_dropped = int((~keep).sum())
    assert counts_e.sum() == n_assign - n_dropped
    assert (counts_e <= G * C).all()

    # cumsum offsets: tile-aligned, monotone, and they bound every index
    seg = -(-counts_e // A) * A
    ends = np.cumsum(seg)
    off = ends - seg
    assert (np.diff(ends) >= 0).all() and ends[-1] <= m_max
    kept_dest = dest[keep]
    kept_e = np.asarray(flat_ids)[keep]
    assert (kept_dest < m_max).all()
    assert (kept_dest >= off[kept_e]).all()
    assert (kept_dest < off[kept_e] + counts_e[kept_e]).all()
    # one packed row per kept assignment (the scatter never collides)
    assert len(np.unique(kept_dest)) == keep.sum()
    # dropped assignments land on the dump row
    assert (dest[~keep] == m_max).all()

    # the tile->expert map covers exactly the occupied segments
    n_live_tiles = int((tile_expert >= 0).sum())
    assert n_live_tiles == seg.sum() // A
    for t, e in enumerate(tile_expert):
        if e >= 0:
            assert counts_e[e] > 0
            assert off[e] <= t * A < ends[e]
        else:
            assert t * A >= ends[-1]


# ------------------------------------------------ path equivalence (moe)

def _pruned_moe_layer(E, top_k, cf, seed, d=32, d_ff=32, block=16):
    spec = MoESpec(n_experts=E, top_k=top_k, d_ff=d_ff, capacity_factor=cf)
    params = moe.init_moe(jax.random.PRNGKey(seed), d, spec, jnp.float32)
    rng = np.random.default_rng(seed)
    for nm in ("gate", "up", "down"):
        if nm not in params:
            continue
        w = np.array(params[nm])
        for e in range(E):
            bm = rng.random((w.shape[1] // block, w.shape[2] // block)) < 0.6
            bm[0, 0] = True
            w[e] = np.where(np.repeat(np.repeat(bm, block, 0), block, 1),
                            w[e], 0.0)
        params[nm] = jnp.asarray(w)
    packed = {(0, nm): pack_expert_projection(params[nm], block=block,
                                              group=True, ragged=True)
              for nm in ("gate", "up", "down") if nm in params}
    return spec, params, packed


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=6),    # n_experts
       st.integers(min_value=1, max_value=3),    # top_k (clamped)
       st.floats(min_value=0.5, max_value=1.5),  # capacity_factor
       st.integers(min_value=1, max_value=3),    # batch
       st.integers(min_value=0, max_value=10**6))  # seed
def test_ragged_grouped_loop_dense_identical(E, top_k, cf, B, seed):
    """ragged == grouped == loop bitwise, and all within float-noise of
    the dense einsum, token-for-token, on arbitrary valid MoE shapes."""
    top_k = min(top_k, E)
    spec, params, packed = _pruned_moe_layer(E, top_k, cf, seed)
    bp = {"moe": params}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 1, 32),
                         jnp.float32)
    y_dense, _ = moe.apply_moe(params, spec, x)
    y_rag = sparse_apply_moe(bp, spec, x, packed, 0,
                             ragged_moe=True)
    y_grp = sparse_apply_moe(bp, spec, x, packed, 0,
                             group_experts=True, ragged_moe=False)
    y_loop = sparse_apply_moe(bp, spec, x, packed, 0,
                              group_experts=False, ragged_moe=False)
    np.testing.assert_array_equal(np.asarray(y_rag), np.asarray(y_grp))
    np.testing.assert_array_equal(np.asarray(y_rag), np.asarray(y_loop))
    scale = float(jnp.abs(y_dense).max()) + 1e-9
    assert float(jnp.abs(y_rag - y_dense).max() / scale) < 1e-5


def test_ragged_falls_back_to_grouped_on_prefill_sizes():
    """Above RAGGED_TOKENS_MAX the ragged knob defers to the grouped
    capacity-slot launch (and stays output-identical)."""
    from repro.kernels import counters
    spec, params, packed = _pruned_moe_layer(4, 2, 1.25, 3)
    bp = {"moe": params}
    S = RAGGED_TOKENS_MAX + 1
    x = jax.random.normal(jax.random.PRNGKey(9), (1, S, 32), jnp.float32)
    counters.reset()
    y_rag = sparse_apply_moe(bp, spec, x, packed, 0,
                             ragged_moe=True)
    assert "grouped_block_sparse_ragged" not in counters.snapshot()
    y_grp = sparse_apply_moe(bp, spec, x, packed, 0,
                             group_experts=True, ragged_moe=False)
    np.testing.assert_array_equal(np.asarray(y_rag), np.asarray(y_grp))


# ------------------------------- serving: determinism + no-retrace pins

@pytest.fixture(scope="module")
def ragged_artifact(tmp_path_factory):
    """Mosaic-pruned MoE model packed with ragged_moe=True, saved and
    reloaded (the flag must survive the bundle round-trip)."""
    from repro.core.artifact import PrunedArtifact
    from repro.core.pipeline import MosaicPipeline
    from repro.core.recipe import CalibrationSpec, PruneRecipe
    from repro.models import transformer as T
    from tests.test_moe_sparse import moe_config
    cfg = moe_config()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    recipe = PruneRecipe(arch=cfg.name, p=0.65, category="unstructured",
                         selector="wanda_block", block=16, ragged_moe=True,
                         calibration=CalibrationSpec(4, 2, 16))
    art = MosaicPipeline(recipe).run(params, cfg)
    d = str(tmp_path_factory.mktemp("ragged-moe"))
    art.save(d)
    return art, PrunedArtifact.load(d)


def test_ragged_flag_rides_plans_and_artifact(ragged_artifact):
    from repro.serve.sparse import PackedExpertProjection
    art, loaded = ragged_artifact
    assert art.report["pack"]["ragged_moe"] is True
    for packed in (art.packed, loaded.packed):
        stacks = [p for p in packed.values()
                  if isinstance(p, PackedExpertProjection)]
        assert stacks and all(p.ragged for p in stacks)
    arrays, meta = plans_to_host(art.packed)
    back = plans_from_host(arrays, meta)
    assert all(p.ragged for p in back.values()
               if isinstance(p, PackedExpertProjection))


def test_ragged_serving_token_identical_and_deterministic(ragged_artifact):
    """Sampled tokens through the ragged decode path equal the dense
    engine's per request, survive shuffled arrival order, and the
    occupancy-dependent dispatch never retraces across ticks."""
    from repro.serve.batching import ContinuousEngine
    from repro.serve.config import ServeConfig
    from repro.serve.scheduler import Request

    art, loaded = ragged_artifact
    rng = np.random.default_rng(4)

    def reqs(order):
        rs = [Request(uid=i, prompt=rng_prompts[i],
                      max_new_tokens=6, temperature=0.8, seed=100 + i)
              for i in order]
        return rs

    rng_prompts = {i: rng.integers(0, 256, (n,)).tolist()
                   for i, n in enumerate([5, 9, 7])}
    kw = dict(max_slots=2, max_seq=32, compute_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    dense, _ = ContinuousEngine(art.params, art.cfg,
                                ServeConfig(**kw)).run(reqs([0, 1, 2]))
    by_uid = {f.request.uid: f.tokens for f in dense}

    eng = ContinuousEngine(art.params, art.cfg, ServeConfig(**kw),
                           packed=art.packed)
    for order in ([0, 1, 2], [2, 0, 1], [1, 2, 0]):
        finished, _ = eng.run(reqs(order))
        for f in finished:
            assert f.tokens == by_uid[f.request.uid], \
                f"uid {f.request.uid} diverged at arrival order {order}"
    # occupancy changes per tick; the trace must not
    assert eng._decode_sample._cache_size() == 1

    # and rehydrated from the bundle, same tokens (plans carry ragged)
    loaded_eng = ContinuousEngine.from_artifact(loaded, ServeConfig(**kw))
    finished, _ = loaded_eng.run(reqs([0, 1, 2]))
    for f in finished:
        assert f.tokens == by_uid[f.request.uid]


def test_ragged_static_engine_token_identical(ragged_artifact):
    """The static engine on ragged-packed plans (in-memory AND loaded)
    matches dense token-for-token; decode batches are ragged-eligible."""
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Engine

    art, loaded = ragged_artifact
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                art.cfg.vocab)
    sc = ServeConfig(max_seq=24, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
    dense = np.asarray(Engine(art.params, art.cfg, sc).generate(prompt, 8))
    for eng in (Engine(art.params, art.cfg, sc, packed=art.packed),
                Engine.from_artifact(loaded, sc)):
        np.testing.assert_array_equal(
            dense, np.asarray(eng.generate(prompt, 8)))
