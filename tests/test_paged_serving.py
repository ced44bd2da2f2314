"""Paged KV pool: block allocator + copy-on-write, prefix sharing,
chunked prefill, admission backpressure, and token-identity of the paged
continuous engine against the contiguous pool — dense, block-sparse and
grouped-MoE, in-memory and from a loaded artifact — plus the redesigned
ServeConfig construction surface (traced per-slot sampling: mixed
temperatures without retracing, per-request seeds independent of batch
composition)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig)
from repro.serve import batching
from repro.serve.batching import ContinuousEngine
from repro.serve.config import ServeConfig
from repro.serve.engine import Engine
from repro.serve.paging import BlockAllocator, OutOfBlocks, PrefixCache
from repro.serve.scheduler import Request, Scheduler


# ------------------------------------------------------------ ServeConfig

def test_serveconfig_validation_and_derived():
    cfg = ServeConfig(max_slots=4, max_seq=64, block_size=8)
    assert cfg.paged and cfg.blocks_per_seq == 8
    assert cfg.arena_blocks == 4 * 64 // 8          # contiguous byte budget
    assert ServeConfig(max_seq=64, block_size=8, n_blocks=5).arena_blocks == 5
    assert not ServeConfig().paged
    with pytest.raises(ValueError):
        ServeConfig(max_seq=60, block_size=8)       # not a block multiple
    with pytest.raises(ValueError):
        ServeConfig(max_seq=64, block_size=8, prefill_chunk=12)
    with pytest.raises(ValueError):
        ServeConfig(prefill_chunk=16)               # chunking needs paging
    with pytest.raises(ValueError):
        ServeConfig(paged_kernel=True)              # kernel needs paging
    assert ServeConfig(max_seq=64, block_size=8,
                       paged_kernel=True).paged_kernel


def test_legacy_kwarg_constructors_warn(served):
    params, cfg = served
    with pytest.warns(DeprecationWarning):
        Engine(params, cfg, 32, compute_dtype=jnp.float32)
    with pytest.warns(DeprecationWarning):
        ContinuousEngine(params, cfg, max_slots=2, max_seq=32,
                         compute_dtype=jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Engine(params, cfg, ServeConfig(max_seq=32))
        ContinuousEngine(params, cfg,
                         ServeConfig(max_slots=2, max_seq=32))


# --------------------------------------------------------- block allocator

def test_allocator_alloc_release_refcount():
    a = BlockAllocator(n_blocks=4, block_size=8)
    assert a.n_free == 4 and a.scratch == 4
    b = a.alloc(3)
    assert len(set(b)) == 3 and a.n_free == 1
    assert all(a.refcount(x) == 1 for x in b)
    a.retain(b[:1])
    assert a.refcount(b[0]) == 2
    a.release(b)                        # shared block survives one release
    assert a.refcount(b[0]) == 1 and a.n_free == 3
    a.release(b[:1])
    assert a.n_free == 4
    with pytest.raises(OutOfBlocks):
        a.alloc(5)
    with pytest.raises(ValueError):
        a.release([0])                  # not allocated
    with pytest.raises(ValueError):
        a.retain([0])


def test_copy_on_write_shared_block():
    a = BlockAllocator(n_blocks=4, block_size=2)
    pool = {"k": jnp.arange(10, dtype=jnp.float32).reshape(5, 2)}
    (b,) = a.alloc(1)
    a.retain([b])                       # two readers
    table = np.array([b, a.scratch], np.int32)
    pool2 = a.ensure_writable(table, 0, pool)
    fresh = int(table[0])
    assert fresh != b                   # writer got a private copy
    np.testing.assert_array_equal(np.asarray(pool2["k"][fresh]),
                                  np.asarray(pool["k"][b]))
    assert a.refcount(b) == 1 and a.refcount(fresh) == 1
    # exclusive blocks are left alone
    assert a.ensure_writable(table, 0, pool2) is pool2
    assert int(table[0]) == fresh


def test_cow_at_zero_free_blocks_uses_reserve():
    """COW against a full arena: without an admission-time reserve the
    allocator raises OutOfBlocks mid-tick (the pre-fix failure, which
    killed serve_forever); with the reserve the copy always succeeds."""
    a = BlockAllocator(n_blocks=3, block_size=2)
    pool = {"k": jnp.arange(8, dtype=jnp.float32).reshape(4, 2)}
    (shared,) = a.alloc(1)
    a.retain([shared])              # a second reader (prefix cache)
    (reserve,) = a.alloc(1)         # claimed at admission for COW
    a.alloc(1)                      # the rest of the arena is busy
    assert a.n_free == 0
    table = np.array([shared, a.scratch], np.int32)
    with pytest.raises(OutOfBlocks):
        a.ensure_writable(table, 0, pool)
    assert int(table[0]) == shared          # failure mutated nothing
    pool2 = a.ensure_writable(table, 0, pool, reserve=reserve)
    assert int(table[0]) == reserve
    np.testing.assert_array_equal(np.asarray(pool2["k"][reserve]),
                                  np.asarray(pool["k"][shared]))
    assert a.refcount(shared) == 1 and a.refcount(reserve) == 1


def test_prefix_cache_share_and_mismatch():
    a = BlockAllocator(n_blocks=8, block_size=4)
    pc = PrefixCache(a)
    assert pc.shareable_tokens(range(8)) == 4   # writer keeps its tail
    assert pc.shareable_tokens(range(9)) == 8
    assert pc.shareable_tokens(range(4)) == 0
    prompt = list(range(100, 110))              # 10 tokens -> 2 full blocks
    owned = a.alloc(3)
    pc.register("sys", prompt, owned)
    assert len(pc) == 1 and a.refcount(owned[0]) == 2
    assert pc.match("sys", prompt) == owned[:2]
    assert pc.match("sys", prompt[:9] + [999]) == owned[:2]  # same prefix
    # divergent tail: longest block-aligned common run still shares
    assert pc.match("sys", prompt[:6] + [777, 778, 779, 780]) == owned[:1]
    assert pc.match("sys", [999] + prompt[1:]) == []    # token mismatch
    assert pc.match("other", prompt) == []
    assert pc.match(None, prompt) == []
    pc.register("sys", [1, 2, 3, 4, 5], owned)  # first writer wins
    assert pc.match("sys", prompt) == owned[:2]
    a.release(owned)
    pc.drop_all()                               # cache's own refs released
    assert a.n_free == 8 and len(pc) == 0


def test_scheduler_prefilling_state_and_backpressure():
    s = Scheduler(max_slots=4, max_seq=32)
    for i in range(3):
        s.submit(Request(uid=i, prompt=[1, 2, 3], max_new_tokens=2))
    slots = s.admissions(can_admit=lambda r: r.uid < 2)
    assert [sl.request.uid for sl in slots] == [0, 1]   # head 2 held, FIFO
    assert set(s.prefilling) == {sl.index for sl in slots}
    assert s.concurrency() == 2 and not s.slots and s.has_work()
    s.started(slots[0], first_token=7)
    assert slots[0].index in s.slots
    assert slots[0].index not in s.prefilling
    assert s.concurrency() == 2                 # one decoding + one prefilling


# ----------------------------------------------- paged vs contiguous serve

@pytest.fixture(scope="module")
def served():
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
    cfg = ModelConfig(name="pgd", d_model=64, vocab=256,
                      vocab_pad_multiple=16,
                      pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),),
                      n_periods=2, scan_layers=False, remat=False)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


BASE = dict(max_slots=3, max_seq=32, compute_dtype=jnp.float32,
            cache_dtype=jnp.float32, prefill_multiple=4)


def _mixed_requests(vocab=256, n_new=8):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(1, vocab, (n,)).tolist(),
                    max_new_tokens=n_new)
            for i, n in enumerate([5, 11, 3, 17, 9, 2])]


def _tokens(finished):
    return [f.tokens for f in sorted(finished, key=lambda f: f.request.uid)]


def test_paged_matches_contiguous_dense(served):
    params, cfg = served
    ref, _ = ContinuousEngine(params, cfg, ServeConfig(**BASE)).run(
        _mixed_requests())
    for extra in ({"block_size": 8},            # one-shot prefill
                  {"block_size": 8, "prefill_chunk": 8},   # chunked
                  {"block_size": 4, "n_blocks": 30}):      # odd arena
        got, stats = ContinuousEngine(
            params, cfg, ServeConfig(**BASE, **extra)).run(_mixed_requests())
        assert _tokens(got) == _tokens(ref), extra
        assert stats.rejected == 0


def test_prefix_sharing_identical_and_counted(served):
    params, cfg = served
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 256, (19,)).tolist()
    reqs = lambda: [Request(uid=i, prompt=prefix + [50 + i],  # noqa: E731
                            max_new_tokens=6, prefix_id="sys")
                    for i in range(5)]
    ref, _ = ContinuousEngine(params, cfg, ServeConfig(**BASE)).run(reqs())
    got, stats = ContinuousEngine(
        params, cfg,
        ServeConfig(**BASE, block_size=8, prefill_chunk=8)).run(reqs())
    assert _tokens(got) == _tokens(ref)
    # later requests mapped the registered prompt blocks instead of
    # prefilling them: 19-token prompt -> 2 shareable full blocks
    shared = [f.prompt_blocks_shared
              for f in sorted(got, key=lambda f: f.request.uid)]
    assert max(shared) == 2 and stats.prompt_blocks_shared >= 4
    assert 0 < stats.prefix_hit_rate <= 1
    assert stats.prefill_chunks > stats.prefills    # chunking really ran


def test_paged_kernel_token_identical_dense(served):
    """The fused Pallas decode kernel is token-identical to the gather
    path (which is itself token-identical to the contiguous pool) across
    one-shot, chunked, and odd-arena paged configs."""
    params, cfg = served
    ref, _ = ContinuousEngine(params, cfg, ServeConfig(**BASE)).run(
        _mixed_requests())
    for extra in ({"block_size": 8},
                  {"block_size": 8, "prefill_chunk": 8},
                  {"block_size": 4, "n_blocks": 30}):
        got, stats = ContinuousEngine(
            params, cfg,
            ServeConfig(**BASE, paged_kernel=True, **extra)
        ).run(_mixed_requests())
        assert _tokens(got) == _tokens(ref), extra
        assert stats.rejected == 0


def test_paged_kernel_prefix_sharing_identical(served):
    """Fused kernel under prefix sharing: decode reads shared arena
    blocks through several slots' tables and must match the gather
    path token-for-token."""
    params, cfg = served
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 256, (19,)).tolist()
    reqs = lambda: [Request(uid=i, prompt=prefix + [50 + i],  # noqa: E731
                            max_new_tokens=6, prefix_id="sys")
                    for i in range(5)]
    serve = dict(**BASE, block_size=8, prefill_chunk=8)
    ref, _ = ContinuousEngine(params, cfg, ServeConfig(**serve)).run(reqs())
    got, stats = ContinuousEngine(
        params, cfg,
        ServeConfig(**serve, paged_kernel=True)).run(reqs())
    assert _tokens(got) == _tokens(ref)
    assert stats.prompt_blocks_shared >= 4 and stats.rejected == 0


def test_paged_arena_round_trip_matches_4d_layout():
    """``paged_write`` then ``paged_gather`` on the ``(n_blocks + 1, bs,
    n_kv * D)`` arena give, element for element, what a ``(n_blocks + 1,
    bs, n_kv, D)`` arena gives under the same block tables, padding
    rows redirected to the scratch block."""
    rng = np.random.default_rng(5)
    nb, bs, n_kv, D, B, S, M = 12, 4, 2, 16, 3, 6, 4
    arena4 = rng.normal(size=(nb + 1, bs, n_kv, D)).astype(np.float32)
    new = rng.normal(size=(B, S, n_kv, D)).astype(np.float32)
    tables = rng.permutation(nb)[:B * M].reshape(B, M).astype(np.int32)
    start = np.array([0, 5, 9], np.int32)
    n_valid = np.array([6, 2, 4], np.int32)
    # the (.., n_kv, D) layout written out: each position's row, scatter
    pos = start[:, None] + np.arange(S)[None, :]
    rows = tables[np.arange(B)[:, None], pos // bs] * bs + pos % bs
    rows = np.where(np.arange(S)[None, :] < n_valid[:, None], rows,
                    (nb + 1) * bs - 1)
    flat4 = arena4.reshape(-1, n_kv, D).copy()
    flat4[rows.reshape(-1)] = new.reshape(B * S, n_kv, D)
    view_rows = (tables[:, :, None] * bs + np.arange(bs)).reshape(B, -1)
    want = flat4[view_rows]

    arena3 = jnp.asarray(arena4.reshape(nb + 1, bs, n_kv * D))
    written = L.paged_write(arena3, jnp.asarray(new), jnp.asarray(tables),
                            jnp.asarray(start), jnp.asarray(n_valid))
    assert written.shape == arena3.shape
    np.testing.assert_array_equal(np.asarray(written).reshape(flat4.shape),
                                  flat4)
    got = L.paged_gather(written, jnp.asarray(tables), n_kv)
    assert got.shape == (B, M * bs, n_kv, D)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("extra", [
    {}, {"block_size": 8, "prefill_chunk": 8},
    {"block_size": 8, "prefill_chunk": 8, "paged_kernel": True}],
    ids=["contiguous", "paged", "paged_kernel"])
def test_donated_pool_served_twice_token_identical(served, monkeypatch,
                                                   extra):
    """With the pool donated to the steps, as on an accelerator, an
    engine served twice stays token-identical to a fresh engine's run,
    prefix sharing (copy-on-write) included, and every pool a donating
    step was given is gone after it: nothing reads a donated buffer."""
    params, cfg = served
    serve = ServeConfig(**BASE, **extra)
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 256, (19,)).tolist()
    reqs = lambda: _mixed_requests() + [  # noqa: E731
        Request(uid=10 + i, prompt=prefix + [50 + i], max_new_tokens=6,
                prefix_id="sys") for i in range(3)]
    ref, _ = ContinuousEngine(params, cfg, serve).run(reqs())

    monkeypatch.setattr(batching, "pool_donation", lambda: (1,))
    eng = ContinuousEngine(params, cfg, serve)
    given = []
    donating = ["_decode_sample"] + (["_prefill"] if serve.paged else [])
    for name in donating:
        def spy(params, pool, *a, _step=getattr(eng, name)):
            given.append(pool)
            return _step(params, pool, *a)
        setattr(eng, name, spy)
    for _ in range(2):
        got, stats = eng.run(reqs())
        assert _tokens(got) == _tokens(ref)
        assert stats.rejected == 0
    leaves = jax.tree.leaves(given)
    assert leaves and all(leaf.is_deleted() for leaf in leaves)


def test_cow_reserve_claimed_at_admission(served, monkeypatch):
    """Satellite regression: the COW copy block must be pre-claimed at
    admission for prefix-sharing requests, so ``ensure_writable`` never
    allocates inside the tick loop. The spy (a) asserts sharing slots
    carry a reserve even at zero free blocks, and (b) *forces* the COW
    path (unreachable organically: only pre-tail prompt blocks are ever
    shared) by simulating a stale reader — exercising the
    reserve-consumption and ownership-swap bookkeeping end to end."""
    params, cfg = served
    calls, cow = [], []
    orig = BlockAllocator.ensure_writable

    def spy(self, table, j, pool, reserve=None):
        calls.append((self.n_free, reserve))
        if reserve is not None and int(table[j]) != reserve:
            b = int(table[j])
            self.retain([b])            # stale reader forces the copy
            pool = orig(self, table, j, pool, reserve=reserve)
            self.release([b])
            assert int(table[j]) == reserve
            cow.append(b)
            return pool
        return orig(self, table, j, pool, reserve=reserve)

    monkeypatch.setattr(BlockAllocator, "ensure_writable", spy)
    rng = np.random.default_rng(4)
    prefix = rng.integers(1, 256, (17,)).tolist()
    reqs = lambda: [Request(uid=i, prompt=prefix + [60 + i],  # noqa: E731
                            max_new_tokens=6, prefix_id="sys")
                    for i in range(4)]
    # 2 slots x 3 blocks fills the 6-block arena exactly; the second
    # wave shares 2 prefix blocks and still fits (1 owned + 1 reserve)
    serve = ServeConfig(**{**BASE, "max_slots": 2}, block_size=8,
                        prefill_chunk=8, n_blocks=6)
    fin, stats = ContinuousEngine(params, cfg, serve).run(reqs())
    assert len(fin) == 4 and stats.rejected == 0
    assert cow, "forced COW never fired"
    # sharing slots reached the COW guard with zero free blocks AND a
    # pre-claimed reserve: the pre-fix code would have raised OutOfBlocks
    assert any(free == 0 and r is not None for free, r in calls)
    # token identity survives the forced copies
    monkeypatch.setattr(BlockAllocator, "ensure_writable", orig)
    ref, _ = ContinuousEngine(params, cfg, serve).run(reqs())
    assert _tokens(fin) == _tokens(ref)


def test_admission_backpressure_out_of_blocks(served):
    params, cfg = served
    # arena of 8 blocks, each request needs 4 (16-token cap / bs 4):
    # only 2 requests can hold cache at once even with 3 slots free
    serve = ServeConfig(**{**BASE, "max_seq": 16}, block_size=4, n_blocks=8)
    reqs = [Request(uid=i, prompt=[7] * 6, max_new_tokens=10)
            for i in range(5)]
    finished, stats = ContinuousEngine(params, cfg, serve).run(reqs)
    assert len(finished) == 5 and stats.rejected == 0
    assert stats.peak_concurrency == 2
    # FIFO: completion order == arrival order under backpressure
    assert [f.request.uid for f in
            sorted(finished, key=lambda f: f.finished_at)] == list(range(5))


def test_oversized_request_rejected_not_deadlocked(served):
    params, cfg = served
    serve = ServeConfig(**{**BASE, "max_seq": 16}, block_size=4, n_blocks=2)
    reqs = [Request(uid=0, prompt=[7] * 6, max_new_tokens=10),  # needs 4
            Request(uid=1, prompt=[7] * 2, max_new_tokens=2)]   # needs 1
    finished, stats = ContinuousEngine(params, cfg, serve).run(reqs)
    assert stats.rejected == 1
    assert [f.request.uid for f in finished] == [1]


def test_paged_rejects_hybrid_configs():
    from tests.conftest import small_config
    cfg = small_config(mamba=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attention-only"):
        ContinuousEngine(params, cfg,
                         ServeConfig(max_slots=2, max_seq=32, block_size=8,
                                     prefill_multiple=1))


# ------------------------------------- block-sparse / MoE paged fast path

@pytest.fixture(scope="module")
def pruned_moe(tmp_path_factory):
    """Mosaic-pruned dense-MLP + MoE model, saved and reloaded."""
    from repro.core.artifact import PrunedArtifact
    from repro.core.pipeline import MosaicPipeline
    from repro.core.recipe import CalibrationSpec, PruneRecipe
    from tests.test_moe_sparse import moe_config
    cfg = moe_config()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    recipe = PruneRecipe(arch=cfg.name, p=0.65, category="unstructured",
                         selector="wanda_block", block=16,
                         calibration=CalibrationSpec(4, 2, 16))
    art = MosaicPipeline(recipe).run(params, cfg)
    d = str(tmp_path_factory.mktemp("paged-moe"))
    art.save(d)
    return art, PrunedArtifact.load(d)


def test_paged_sparse_moe_token_identical(pruned_moe):
    """The paged pool composes with the block-sparse serving fast path:
    dense-contiguous == sparse-paged (grouped MoE kernel), in-memory and
    rehydrated from the artifact bundle."""
    art, loaded = pruned_moe
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, (n,)).tolist(),
                    max_new_tokens=6)
            for i, n in enumerate([5, 9, 7])]
    kw = dict(max_slots=2, max_seq=32, compute_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    ref, _ = ContinuousEngine(art.params, art.cfg,
                              ServeConfig(**kw)).run(reqs)
    paged = ServeConfig(**kw, block_size=8, prefill_chunk=8)
    fused = ServeConfig(**kw, block_size=8, prefill_chunk=8,
                        paged_kernel=True)
    variants = {
        "mem-sparse": ContinuousEngine(art.params, art.cfg, paged,
                                       packed=art.packed),
        "load-sparse": ContinuousEngine.from_artifact(loaded, paged),
        "mem-sparse-kernel": ContinuousEngine(art.params, art.cfg, fused,
                                              packed=art.packed),
        "load-sparse-kernel": ContinuousEngine.from_artifact(loaded, fused),
    }
    for label, eng in variants.items():
        got, stats = eng.run(reqs)
        assert _tokens(got) == _tokens(ref), label
        assert stats.rejected == 0


# ------------------------------------------------- traced per-slot sampling

def test_mixed_temperatures_do_not_retrace(served):
    params, cfg = served
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(1, 256, (4,)).tolist(),
                    max_new_tokens=6, temperature=t, seed=i)
            for i, t in enumerate([0.0, 0.7, 1.3])]
    eng = ContinuousEngine(params, cfg, ServeConfig(**BASE))
    finished, _ = eng.run(reqs)
    assert len(finished) == 3
    # temperature is a traced vector, not a static arg: one trace total
    assert eng._decode_sample._cache_size() == 1
    # and the greedy request really decoded greedily
    ref, _ = eng.run([Request(uid=0, prompt=reqs[0].prompt,
                              max_new_tokens=6)])
    assert _tokens(finished)[0] == ref[0].tokens


def test_request_seed_independent_of_batch(served):
    params, cfg = served
    probe = lambda uid: Request(uid=uid, prompt=[9, 8, 7],  # noqa: E731
                                max_new_tokens=6, temperature=0.9, seed=123)
    eng = ContinuousEngine(params, cfg, ServeConfig(**BASE))
    alone, _ = eng.run([probe(0)])
    noise = [Request(uid=i, prompt=[i + 1] * 5, max_new_tokens=6,
                     temperature=0.5, seed=i) for i in range(1, 3)]
    crowded, _ = eng.run([probe(0)] + noise)
    assert alone[0].tokens == _tokens(crowded)[0]
    # same stream on the paged pool too
    paged = ContinuousEngine(params, cfg, ServeConfig(**BASE, block_size=8))
    crowded_paged, _ = paged.run([probe(0)] + noise)
    assert alone[0].tokens == _tokens(crowded_paged)[0]
