"""Block-sparse serving path: pruned model -> kernel plans -> exact
agreement with the dense forward, with real tile-skip fractions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.prune_controller import run_pruning_controller
from repro.core.rank_controller import run_ranking_controller
from repro.models import transformer as T
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig)
from repro.serve.sparse import (flop_savings, pack_model, pack_projection,
                                sparse_apply_mlp, sparse_linear)


@pytest.fixture(scope="module")
def pruned():
    # dims chosen as multiples of the kernel block (128)
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
    return res.params, res.cfg, batches[0]


def test_pack_model_finds_skippable_tiles(pruned):
    params, cfg, _ = pruned
    packed = pack_model(params, cfg, block=16)
    assert packed, "no projections packed"
    sav = flop_savings(packed)
    assert 0.3 < sav <= 0.95  # block=16 matches the wanda_block mask tile       # wanda_block at p=0.75 leaves zero tiles


def test_sparse_linear_matches_dense(pruned):
    params, cfg, _ = pruned
    w = params["blocks"][0]["mlp"]["up"]
    packed = pack_projection(w, block=16)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, w.shape[0]))
    y_sparse = sparse_linear(x, w, packed)
    np.testing.assert_allclose(np.asarray(y_sparse), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-5)


def test_sparse_mlp_matches_dense(pruned):
    params, cfg, toks = pruned
    packed = pack_model(params, cfg, block=16)
    spec = cfg.layer(0).ffn
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model))
    from repro.models.layers import apply_mlp
    y_dense = apply_mlp(params["blocks"][0]["mlp"], spec, x)
    y_sparse = sparse_apply_mlp(params["blocks"][0], spec, x, packed,
                                layer=0)
    np.testing.assert_allclose(np.asarray(y_sparse), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)


def test_non_tileable_projection_returns_none():
    w = jnp.ones((100, 200))       # not multiples of 128
    assert pack_projection(w, block=16) is None
