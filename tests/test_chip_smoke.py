"""CPU rehearsal of ``chip_smoke.py``: the same phase functions, at a
width the CPU runs in seconds (Pallas kernels in interpret mode, the
gateway on an ephemeral port), and ``main()`` refusing to run without a
TPU."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

# phi3's layer at a tiny width; GQA kept (8 q heads on 2 kv heads) and
# every width a multiple of the 128 plan tile so the MLPs pack
REHEARSAL = smoke.SmokeConfig(
    n_periods=2,
    widths=dict(d_model=256, n_q=8, n_kv=2, head_dim=32, d_ff=512,
                vocab=512),
    calib_samples=2, calib_batch=2, calib_seq=32, max_seq=128,
    prefill_chunk=32, prefix_len=32, tails=(5, 21), solo_lens=(40, 3),
    new_tokens=6)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    sc = REHEARSAL
    params, cfg = smoke.build_model(sc)
    art, seconds = smoke.prune(sc, params, cfg)
    loaded = smoke.save_and_load(art, str(tmp_path_factory.mktemp("art")))
    eng = smoke.build_engine(sc, loaded)
    bodies, reqs = smoke.make_requests(sc, loaded.cfg.vocab)
    direct = smoke.serve_direct(eng, reqs)
    return dict(sc=sc, cfg=cfg, art=art, loaded=loaded, eng=eng,
                bodies=bodies, reqs=reqs, direct=direct, seconds=seconds)


def test_rehearsal_packs_every_mlp_projection(served):
    art = served["art"]
    assert smoke.mlp_skipped(art) == []
    assert art.report["pack"]["flop_savings"] > 0
    loaded = served["loaded"]
    assert loaded.cfg == art.cfg
    # bfloat16 weights survive the npz round trip as bfloat16
    for got, want in zip(jax.tree.leaves(loaded.params),
                         jax.tree.leaves(art.params)):
        assert got.dtype == want.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert served["seconds"] > 0


def test_rehearsal_gateway_matches_engine_run(served):
    direct = served["direct"]
    assert len(direct["tokens"]) == len(served["reqs"])
    events = smoke.serve_gateway(served["eng"], served["bodies"])
    assert smoke.gateway_failures(events, direct["tokens"]) == []


def test_rehearsal_logits_within_tolerance(served):
    errs = smoke.logit_checks(served["sc"], served["loaded"])
    assert set(errs) == {"prefill_sparse_vs_dense", "decode_sparse_vs_dense",
                         "decode_kernel_vs_gather"}
    for name, err in errs.items():
        assert err <= smoke.LOGIT_TOL, (name, err)


def test_rehearsal_kernel_oracles(served):
    errs = smoke.kernel_oracles(served["sc"], served["cfg"])
    assert len(errs) == 5
    for name, err in errs.items():
        assert err <= smoke.KERNEL_TOL, (name, err)


def test_rehearsal_decode_step_interprets_on_cpu(served):
    # the kernels are in the step, interpreted: the CPU backend chooses
    # interpret mode by itself, so no tpu_custom_call is lowered here
    text = smoke.decode_step_text(served["sc"], served["eng"])
    assert "tpu_custom_call" not in text
    assert "ENTRY" in text


def test_main_exits_nonzero_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert "no TPU found" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out
