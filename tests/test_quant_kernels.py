"""Sparse × quantized kernels: the int8 kept-tile path.

The numerics oracle is *bitwise* identity: pow2 per-tile scales commute
with every float rounding in the accumulation, so each quantized kernel
(block-sparse, grouped, ragged) must equal the unquantized kernel run
over the fake-quant (dequantised) weights exactly — in f32 AND bf16.
On top of that: per-tile round-trip properties, the per-input-row RTN
regression for ``core.quant``, the recipe→plans→artifact→engine
threading of the quant flag, and mixed quant+grouped+ragged serving
token identity through both engines.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.core.artifact import PrunedArtifact
from repro.core.pipeline import MosaicPipeline
from repro.core.quant import (INT8_MAXQ, QUANT_MODES, dequantize_array,
                              dequantize_tiles, quantize_array,
                              quantize_tiles)
from repro.core.recipe import CalibrationSpec, PruneRecipe
from repro.models import transformer as T
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig, MoESpec)
from repro.serve.batching import ContinuousEngine
from repro.serve.config import ServeConfig
from repro.serve.engine import Engine, make_sparse_mlp_apply
from repro.serve.scheduler import Request
from repro.serve.sparse import (dequantized_weight, pack_expert_projection,
                                pack_projection, plans_from_host,
                                plans_to_host, sparse_linear)

BLOCK = 16


def _block_structured(key, K, N, block=BLOCK, keep=0.4, dtype=jnp.float32):
    kw, km = jax.random.split(key)
    w = jax.random.normal(kw, (K, N), dtype)
    bm = jax.random.uniform(km, (K // block, N // block)) < keep
    return jnp.where(jnp.repeat(jnp.repeat(bm, block, 0), block, 1), w, 0)


# --------------------------------------------------- per-tile round trip

@settings(max_examples=20)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1),
       st.floats(min_value=-8.0, max_value=8.0))
def test_quantize_tiles_properties(seed, log_mag):
    """Positive pow2 scales; per-element error bounded by amax/127; an
    all-zero tile quantises to zeros with scale 1."""
    rng = np.random.default_rng(seed)
    tiles = rng.normal(scale=2.0 ** log_mag, size=(3, 8, 8)).astype(
        np.float32)
    tiles[0] = 0.0
    q, scales = quantize_tiles(tiles)
    assert q.dtype == np.int8 and scales.dtype == np.float32
    assert (scales > 0).all()
    # pow2: log2 is integral
    np.testing.assert_array_equal(np.log2(scales),
                                  np.round(np.log2(scales)))
    assert scales[0] == 1.0 and not q[0].any()
    back = dequantize_tiles(q, scales)
    amax = np.abs(tiles).max(axis=(1, 2))
    bound = amax / INT8_MAXQ + 1e-12
    assert (np.abs(back - tiles).max(axis=(1, 2)) <= bound).all()
    assert (np.abs(q) <= INT8_MAXQ).all()


def test_quantize_tiles_bf16_roundtrip_exact():
    """int8 magnitudes × pow2 scales carry no mantissa bits beyond bf16:
    casting the fake-quant tiles to bf16 and back loses nothing."""
    rng = np.random.default_rng(0)
    q, scales = quantize_tiles(rng.normal(size=(4, BLOCK, BLOCK)))
    fq = dequantize_tiles(q, scales)
    back = np.asarray(jnp.asarray(fq).astype(jnp.bfloat16).astype(
        jnp.float32))
    np.testing.assert_array_equal(fq, back)


# ------------------------------------------- group-wise RTN (core.quant)

def test_quantize_array_groups_per_input_row():
    """Groups run along input rows within one output column — a huge
    outlier in column 0 must not inflate column 1's error."""
    rng = np.random.default_rng(1)
    w = rng.normal(size=(64, 2)).astype(np.float32)
    w[0, 0] = 1e4                             # outlier confined to col 0
    back = dequantize_array(*quantize_array(jnp.asarray(w), bits=8,
                                            group=32))
    err = np.abs(np.asarray(back) - w)
    assert err[:, 1].max() < 0.05             # col 1 unaffected
    assert err[:32, 0].max() > 0.5            # col 0's group pays for it
    assert err[32:, 0].max() < 0.05           # but only the outlier group


def test_quantize_array_shapes_and_padding():
    w = jnp.asarray(np.random.default_rng(2).normal(size=(40, 3, 5)),
                    jnp.float32)
    q, scale, shape, pad = quantize_array(w, bits=8, group=16)
    assert q.shape == (15, 3, 16) and pad == 8     # ceil(40/16) groups
    assert scale.shape == (15, 3, 1)
    back = dequantize_array(q, scale, shape, pad)
    assert back.shape == w.shape
    assert float(jnp.abs(back - w).max()) < 0.05


def test_quantize_model_stats_pinned():
    """Compression stats from real per-column scale counts: 8-bit with
    group=16 on this config stays within the analytic band."""
    from repro.core.quant import quantize_model
    cfg = _cfg()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    qp, stats = quantize_model(params, cfg, bits=8, group=16)
    assert stats["bits"] == 8
    # 2-D projections hit the analytic 16/(8+1) = 1.78x; (E, K, N)
    # expert weights fold E as the group axis, whose short columns pay
    # more scale overhead — the blend on this config is pinned here
    assert stats["compression"] == pytest.approx(1.488, rel=0.02)
    # fake-quant round trip keeps shapes/dtypes
    w0 = params["blocks"][0]["mlp"]["up"]
    assert qp["blocks"][0]["mlp"]["up"].shape == w0.shape


# ------------------------------------------------ kernel bitwise identity

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_quant_bitwise(dtype):
    w = _block_structured(jax.random.PRNGKey(0), 64, 48)
    p = pack_projection(w, BLOCK, quant="int8")
    wfq = jnp.asarray(dequantized_weight(p, 64), dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), dtype)
    y_q = sparse_linear(x, wfq, p, quant="int8")
    y_ref = sparse_linear(x, wfq, p, quant="none")
    assert y_q.dtype == y_ref.dtype
    np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_quant_bitwise(dtype):
    from repro.serve.sparse import grouped_sparse_linear
    E, M, K, N = 3, 8, 64, 48
    keys = jax.random.split(jax.random.PRNGKey(2), E + 1)
    w = jnp.stack([_block_structured(keys[e], K, N) for e in range(E)])
    p = pack_expert_projection(w, BLOCK, quant="int8")
    wfq = jnp.stack([jnp.asarray(dequantized_weight(p.expert(e), K), dtype)
                     for e in range(E)])
    xs = jax.random.normal(keys[-1], (E, M, K), dtype)
    y_q = grouped_sparse_linear(xs, wfq, p, quant="int8")
    y_ref = grouped_sparse_linear(xs, wfq, p, quant="none")
    np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_quant_bitwise(dtype):
    from repro.kernels.grouped_block_sparse.ops import RAGGED_BLOCK_ROWS
    from repro.serve.sparse import ragged_sparse_linear
    E, K, N = 3, 64, 48
    keys = jax.random.split(jax.random.PRNGKey(3), E + 1)
    w = jnp.stack([_block_structured(keys[e], K, N) for e in range(E)])
    p = pack_expert_projection(w, BLOCK, ragged=True, quant="int8")
    wfq = jnp.stack([jnp.asarray(dequantized_weight(p.expert(e), K), dtype)
                     for e in range(E)])
    n_tiles = 4                              # experts 0,1 live; one dead
    tile_expert = jnp.asarray([0, 1, 1, -1], jnp.int32)
    xp = jax.random.normal(keys[-1], (n_tiles * RAGGED_BLOCK_ROWS, K),
                           dtype)
    y_q = ragged_sparse_linear(xp, wfq, tile_expert, p, quant="int8")
    y_ref = ragged_sparse_linear(xp, wfq, tile_expert, p, quant="none")
    live = np.repeat(np.asarray(tile_expert) >= 0, RAGGED_BLOCK_ROWS)
    np.testing.assert_array_equal(np.asarray(y_q)[live],
                                  np.asarray(y_ref)[live])


def test_dequantized_weight_matches_tile_storage():
    """Scattered kept tiles reproduce exactly the fake-quant of the
    planned weight; pruned tiles stay zero."""
    w = _block_structured(jax.random.PRNGKey(4), 64, 48)
    p = pack_projection(w, BLOCK, quant="int8")
    wfq = dequantized_weight(p, 64)
    # zero wherever the plan has no tile
    counts = np.asarray(p.counts)
    kept = np.zeros((64 // BLOCK, 48 // BLOCK), bool)
    idx = np.asarray(p.indices)
    for n in range(counts.shape[0]):
        for s in range(int(counts[n])):
            kept[int(idx[n, s]), n] = True
    mask = np.repeat(np.repeat(kept, BLOCK, 0), BLOCK, 1)
    assert not wfq[~mask].any()
    # kept tiles match a direct tile-by-tile round trip
    err = np.abs(wfq - np.asarray(w))
    assert err.max() <= np.abs(np.asarray(w)).max() / INT8_MAXQ + 1e-12


# ----------------------------------------- recipe → plans → artifact flow

def _cfg() -> ModelConfig:
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
    return ModelConfig(
        name="quant-kernels-test", d_model=64, vocab=256,
        vocab_pad_multiple=16,
        pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),
                 LayerSpec(attn, MoESpec(n_experts=4, top_k=2, d_ff=64))),
        n_periods=1, scan_layers=False, remat=False)


@pytest.fixture(scope="module")
def quant_artifact(tmp_path_factory):
    cfg = _cfg()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    recipe = PruneRecipe(arch=cfg.name, p=0.6, category="unstructured",
                         selector="wanda_block", block=BLOCK,
                         ragged_moe=True, quant="int8",
                         calibration=CalibrationSpec(4, 2, 16))
    art = MosaicPipeline(recipe).run(params, cfg)
    d = str(tmp_path_factory.mktemp("quant-bundle"))
    art.save(d)
    return art, PrunedArtifact.load(d)


def test_recipe_quant_validation():
    with pytest.raises(ValueError, match="quant"):
        PruneRecipe(arch="llama3-8b", p=0.5, quant="fp4")
    assert PruneRecipe(arch="llama3-8b", p=0.5).quant == "none"
    assert "int8" in QUANT_MODES and "none" in QUANT_MODES


def test_quant_flag_reaches_plans_and_report(quant_artifact):
    art, _ = quant_artifact
    assert art.recipe.quant == "int8"
    assert art.report["pack"]["quant"] == "int8"
    qb = art.report["pack"]["quant_bytes"]
    assert qb["per_projection"] and qb["total_bytes"] > 0
    for row in qb["per_projection"].values():
        assert row["tile_bytes"] > 0 and row["bytes"] > row["tile_bytes"]
    assert qb["ratio_vs_bf16"] < 0.5
    assert art.report["bytes_after"] < art.report["bytes_before"]
    for p in art.packed.values():
        assert p.quant == "int8"
        assert p.tiles is not None and p.tiles.dtype == jnp.int8
        assert p.scales is not None and p.slots is not None


def test_quant_plans_host_roundtrip(quant_artifact):
    art, loaded = quant_artifact
    back = plans_from_host(*plans_to_host(art.packed))
    for store in (back, loaded.packed):
        assert set(store) == set(art.packed)
        for k, p in art.packed.items():
            b = store[k]
            assert b.quant == "int8" and b.tiles.dtype == jnp.int8
            np.testing.assert_array_equal(np.asarray(b.tiles),
                                          np.asarray(p.tiles))
            np.testing.assert_array_equal(np.asarray(b.scales),
                                          np.asarray(p.scales))
            np.testing.assert_array_equal(np.asarray(b.slots),
                                          np.asarray(p.slots))


def test_params_are_fake_quantized_at_pack(quant_artifact):
    """stage_pack replaces quantized projections' weights with their
    kept-tile round trip, so dense forward == quantized kernels."""
    art, _ = quant_artifact
    p = art.packed[(0, "up")]
    w = np.asarray(art.params["blocks"][0]["mlp"]["up"], np.float32)
    np.testing.assert_array_equal(
        w.reshape(w.shape[0], -1),
        dequantized_weight(p, w.shape[0]))


# --------------------------------------------------- serving token paths

def test_quant_serving_token_identical(quant_artifact):
    """int8 vs dequantized reference, static engine, in-memory AND
    loaded — all four token streams identical."""
    art, loaded = quant_artifact
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                art.cfg.vocab)
    kw = dict(max_seq=24, compute_dtype=jnp.float32,
              cache_dtype=jnp.float32)

    def gen(params, cfg, packed, quant):
        eng = Engine(params, cfg, ServeConfig(**kw, quant=quant),
                     packed=packed)
        return np.asarray(eng.generate(prompt, 8))

    ref = gen(art.params, art.cfg, art.packed, "none")
    for params, cfg, packed in ((art.params, art.cfg, art.packed),
                                (loaded.params, loaded.cfg, loaded.packed)):
        np.testing.assert_array_equal(ref, gen(params, cfg, packed, "int8"))
        np.testing.assert_array_equal(ref, gen(params, cfg, packed, None))


def test_quant_continuous_engine_token_identical(quant_artifact):
    """Mixed quant + grouped + ragged through the continuous engine:
    in-memory int8, loaded int8, and the reference path all agree."""
    art, loaded = quant_artifact
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, (n,)).tolist(),
                    max_new_tokens=6)
            for i, n in enumerate([5, 9, 7])]
    kw = dict(max_slots=2, max_seq=32, compute_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    engines = {
        "mem-int8": ContinuousEngine(art.params, art.cfg,
                                     ServeConfig(**kw, quant="int8"),
                                     packed=art.packed),
        "load-int8": ContinuousEngine.from_artifact(
            loaded, ServeConfig(**kw, quant="int8")),
        "load-ref": ContinuousEngine.from_artifact(
            loaded, ServeConfig(**kw, quant="none")),
    }
    outs = {}
    for label, eng in engines.items():
        finished, _ = eng.run(reqs)
        outs[label] = sorted((f.request.uid, tuple(f.tokens))
                             for f in finished)
    assert outs["mem-int8"] == outs["load-int8"] == outs["load-ref"]


def test_serve_config_quant_validation(quant_artifact):
    art, _ = quant_artifact
    with pytest.raises(ValueError, match="quant"):
        ServeConfig(quant="fp8")
    assert ServeConfig().quant is None
    # int8 demanded of plans without tile storage fails up front
    bare = {k: dataclasses.replace(p, quant="none", tiles=None,
                                   scales=None, slots=None)
            for k, p in art.packed.items()}
    with pytest.raises(ValueError, match="int8"):
        make_sparse_mlp_apply(bare, quant="int8")
    make_sparse_mlp_apply(art.packed, quant="int8")   # plans carry tiles
