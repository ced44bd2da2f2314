"""Continuous-batching engine: scheduler slot lifecycle, token-for-token
agreement with the static Engine, and the block-sparse serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig)
from repro.serve.batching import ContinuousEngine, latency_percentiles
from repro.serve.engine import Engine
from repro.serve.scheduler import Request, Scheduler


# ------------------------------------------------------------- scheduler
# pure host-side bookkeeping: no jax, instant

def test_scheduler_fifo_admission_and_slot_reuse():
    s = Scheduler(max_slots=2, max_seq=32)
    for i in range(4):
        s.submit(Request(uid=i, prompt=[1, 2, 3], max_new_tokens=2))
    slots = s.admissions()
    assert [sl.request.uid for sl in slots] == [0, 1]
    assert s.admissions() == []                     # pool full
    for sl in slots:
        s.started(sl, first_token=7)
    # one decode tick finishes both (budget 2: prefill token + 1)
    s.decoded({sl.index: 9 for sl in slots})
    assert len(s.finished) == 2
    assert not s.slots
    # freed slots are reused by the next FIFO pair
    slots2 = s.admissions()
    assert [sl.request.uid for sl in slots2] == [2, 3]
    assert {sl.index for sl in slots2} == {sl.index for sl in slots}


def test_scheduler_eos_and_reject():
    s = Scheduler(max_slots=1, max_seq=8)
    s.submit(Request(uid=0, prompt=list(range(8)), max_new_tokens=4))
    assert s.rejected and not s.queue               # prompt + 1 > max_seq
    s.submit(Request(uid=1, prompt=[1, 2], max_new_tokens=50, eos_id=5))
    (sl,) = s.admissions()
    s.started(sl, first_token=3)
    s.decoded({sl.index: 5})                        # EOS
    assert s.finished[-1].reason == "eos"
    assert s.finished[-1].tokens == [3, 5]
    # cache_full: budget larger than the cache can hold
    s.submit(Request(uid=2, prompt=[1, 2], max_new_tokens=50))
    (sl,) = s.admissions()
    s.started(sl, first_token=3)
    for t in range(10):
        if sl.index not in s.slots:
            break
        s.decoded({sl.index: 9})
    assert s.finished[-1].reason == "cache_full"
    assert s.finished[-1].request.uid == 2


def test_scheduler_ignores_stale_slot_tokens():
    # tokens decoded past a finished slot (mid-burst waste) are dropped
    s = Scheduler(max_slots=1, max_seq=32)
    s.submit(Request(uid=0, prompt=[1], max_new_tokens=2))
    (sl,) = s.admissions()
    s.started(sl, first_token=4)
    s.decoded({sl.index: 5})
    assert len(s.finished) == 1
    s.decoded({sl.index: 6})                        # stale: no crash, no-op
    assert s.finished[0].tokens == [4, 5]


# ---------------------------------------------------------------- engine

@pytest.fixture(scope="module")
def served():
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
    cfg = ModelConfig(name="srv", d_model=64, vocab=256,
                      vocab_pad_multiple=16,
                      pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),),
                      n_periods=2, scan_layers=False, remat=False)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def test_vector_cache_index_matches_scalar(served):
    params, cfg = served
    s_max = 32
    pool = T.init_cache_pool(cfg, 2, s_max, jnp.float32)
    lens = [5, 9]
    toks = [jax.random.randint(jax.random.PRNGKey(i + 1), (1, n), 0, 256)
            for i, n in enumerate(lens)]
    for slot, t in enumerate(toks):
        row = T.init_cache(cfg, 1, s_max, jnp.float32)
        _, row, _ = T.forward(params, cfg, t, cache=row,
                              cache_index=jnp.int32(0),
                              compute_dtype=jnp.float32)
        pool = T.write_cache_slot(pool, row, slot)
    new = jnp.array([[7], [11]], jnp.int32)
    lo_vec, _, _ = T.forward(params, cfg, new, cache=pool,
                             cache_index=jnp.asarray(lens, jnp.int32),
                             compute_dtype=jnp.float32)
    for i, (n, t) in enumerate(zip(lens, toks)):
        c = T.init_cache(cfg, 1, s_max, jnp.float32)
        _, c, _ = T.forward(params, cfg, t, cache=c,
                            cache_index=jnp.int32(0),
                            compute_dtype=jnp.float32)
        lo_ref, _, _ = T.forward(params, cfg, new[i:i + 1], cache=c,
                                 cache_index=jnp.int32(n),
                                 compute_dtype=jnp.float32)
        # B=2 and B=1 forwards are different XLA programs: float32
        # reductions may reassociate across batch sizes, which moves the
        # logits by about one ulp (max 1.8e-6 observed), never more
        np.testing.assert_allclose(np.asarray(lo_vec[i]),
                                   np.asarray(lo_ref[0]), rtol=0, atol=1e-5)


def test_cache_pool_requires_unrolled(served):
    _, cfg = served
    with pytest.raises(ValueError):
        T.init_cache_pool(cfg.replace(scan_layers=True), 2, 16)


def test_continuous_hybrid_needs_unpadded_prefill():
    # padded prefill would integrate pad tokens into the SSM state, so
    # hybrid configs are rejected unless prefills are unpadded — and with
    # prefill_multiple=1 the hybrid engine matches the static engine
    from tests.conftest import small_config
    cfg = small_config(mamba=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        ContinuousEngine(params, cfg, max_slots=2, max_seq=32)
    ce = ContinuousEngine(params, cfg, max_slots=2, max_seq=32,
                          compute_dtype=jnp.float32,
                          cache_dtype=jnp.float32, prefill_multiple=1)
    eng = Engine(params, cfg, 32, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, (n,)).tolist(),
                    max_new_tokens=5)
            for i, n in enumerate([6, 9])]
    finished, _ = ce.run(reqs)
    for f in finished:
        p = jnp.asarray([f.request.prompt], jnp.int32)
        ref = eng.generate(p, 5)[0, p.shape[1]:].tolist()
        assert f.tokens == ref, f"uid {f.request.uid} diverged"


def test_cache_full_uses_last_kv_position():
    # a budget larger than the cache stops exactly when the pool is full:
    # prompt s0 + one prefill-sampled token + (max_seq - s0) decode writes
    s = Scheduler(max_slots=1, max_seq=8)
    s.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=50))
    (sl,) = s.admissions()
    s.started(sl, first_token=3)
    while sl.index in s.slots:
        s.decoded({sl.index: 9})
    f = s.finished[-1]
    assert f.reason == "cache_full"
    assert len(f.tokens) == 8 - 2 + 1       # max_seq - s0 + 1


def test_continuous_matches_static_mixed_lengths(served):
    params, cfg = served
    eng = Engine(params, cfg, 64, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    ce = ContinuousEngine(params, cfg, max_slots=3, max_seq=64,
                          compute_dtype=jnp.float32,
                          cache_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    n_new = 10
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, (n,)).tolist(),
                    max_new_tokens=n_new)
            for i, n in enumerate([7, 13, 5, 20, 9])]   # 5 reqs, 3 slots
    finished, stats = ce.run(reqs)
    assert len(finished) == len(reqs)
    assert stats.prefills == len(reqs)                  # slots were reused
    for f in finished:
        p = jnp.asarray([f.request.prompt], jnp.int32)
        ref = eng.generate(p, n_new)[0, p.shape[1]:].tolist()
        assert f.tokens == ref, f"uid {f.request.uid} diverged"
    lat = latency_percentiles(finished)
    assert lat["p99"] >= lat["p50"] > 0


def test_continuous_eos_mid_burst(served):
    params, cfg = served
    eng = Engine(params, cfg, 64, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    ce = ContinuousEngine(params, cfg, max_slots=2, max_seq=64,
                          compute_dtype=jnp.float32,
                          cache_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, (6,)).tolist()
    ref = eng.generate(jnp.asarray([prompt], jnp.int32), 12)[0, 6:].tolist()
    eos = ref[4]
    stop = ref.index(eos) + 1          # first occurrence wins
    finished, _ = ce.run([Request(uid=0, prompt=prompt, max_new_tokens=12,
                                  eos_id=eos)])
    assert finished[0].reason == "eos"
    assert finished[0].tokens == ref[:stop]


@pytest.fixture(scope="module")
def pruned_served():
    from repro.core.prune_controller import run_pruning_controller
    from repro.core.rank_controller import run_ranking_controller
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=32)
    cfg = ModelConfig(name="sp", d_model=128, vocab=256,
                      vocab_pad_multiple=16,
                      pattern=(LayerSpec(attn, MLPSpec(d_ff=256)),),
                      n_periods=2, scan_layers=False, remat=False)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    batches = [jax.random.randint(jax.random.PRNGKey(i), (2, 16), 0,
                                  cfg.vocab) for i in range(2)]
    art = run_ranking_controller(params, cfg, batches)
    res = run_pruning_controller(params, cfg, art, 0.75,
                                 category="unstructured",
                                 selector="wanda_block")
    return res.params, res.cfg


def test_sparse_engine_matches_dense_interpret(pruned_served):
    from repro.serve.sparse import flop_savings, pack_model
    params, cfg = pruned_served
    packed = pack_model(params, cfg, block=16)
    assert packed and flop_savings(packed) > 0.3
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, (n,)).tolist(),
                    max_new_tokens=6)
            for i, n in enumerate([6, 11, 4])]
    kw = dict(max_slots=2, max_seq=48, compute_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    dense, _ = ContinuousEngine(params, cfg, **kw).run(reqs)
    sparse, _ = ContinuousEngine(params, cfg, packed=packed,
                                 **kw).run(reqs)
    for d, s in zip(dense, sparse):
        assert d.tokens == s.tokens, f"uid {d.request.uid} diverged"
