"""Distributed runtime tests. Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process (and all other tests) keep seeing 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str) -> dict:
    """Run `body` under 8 fake devices; body must print a JSON dict."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import json\n" + textwrap.dedent(body))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_pipeline_parallel_matches_sequential():
    res = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_forward
        mesh = Mesh(np.array(jax.devices()[:4]), ("stage",))
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (4, 16, 16)) * 0.3
        def stage_fn(p, x): return jnp.tanh(x @ p["w"])
        x = jax.random.normal(key, (6, 8, 16))
        out = pipeline_forward(stage_fn, {"w": Ws}, x, mesh, axis="stage")
        def seq(x1):
            for i in range(4): x1 = stage_fn({"w": Ws[i]}, x1)
            return x1
        ref = jax.vmap(seq)(x)
        print(json.dumps({"err": float(jnp.abs(out - ref).max())}))
    """)
    assert res["err"] < 1e-6


def test_compressed_psum_accuracy_and_error_feedback():
    res = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        def f(gl):
            red, e = compressed_psum({"g": gl}, "pod")
            return red["g"], e["g"]
        fm = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                           out_specs=(P(), P("pod")))
        red, e = fm(g)
        print(json.dumps({
            "err": float(jnp.abs(red[0] - g.mean(0)).max()),
            "ef_nonzero": float(jnp.abs(e).max()),
        }))
    """)
    assert res["err"] < 0.02          # int8 quantisation error bound
    assert res["ef_nonzero"] > 0      # residual captured for next step


def test_sharded_forward_and_decode():
    res = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed import sharding as SH
        from repro.models import transformer as T
        from repro.models.specs import *
        from repro.serve.engine import make_serve_step
        mesh = Mesh(np.array(jax.devices()).reshape(2,2,2),
                    ("pod","data","model"))
        attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
        cfg = ModelConfig(name="t", d_model=64, vocab=256,
            vocab_pad_multiple=16,
            pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),
                     LayerSpec(MambaSpec(d_inner=128, d_state=16,
                                         head_dim=16, chunk=8),
                               MoESpec(n_experts=4, top_k=2, d_ff=64))),
            n_periods=2, scan_layers=True, remat=False)
        shd = SH.param_shardings(mesh, cfg)
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(jax.device_put, params, shd)
        toks = jnp.zeros((8, 16), jnp.int32)
        f = jax.jit(lambda p, t: T.forward(p, cfg, t)[0],
                    in_shardings=(shd, SH.input_sharding(mesh, 8)))
        lo = f(params, toks)
        cache = jax.tree.map(jax.device_put, T.init_cache(cfg, 8, 32),
                             SH.cache_shardings(mesh, cfg, 8))
        ss = jax.jit(make_serve_step(cfg))
        lo1, _ = ss(params, cache, toks[:, :1], jnp.int32(0))
        # sharded-vs-single-device numerical check (fp32 compute so the
        # comparison isn't dominated by bf16 reduction-order noise)
        f32 = jax.jit(lambda p, t: T.forward(
                          p, cfg, t, compute_dtype=jnp.float32)[0],
                      in_shardings=(shd, SH.input_sharding(mesh, 8)))
        lo32 = f32(params, toks)
        params_h = jax.device_get(params)
        lo_ref = T.forward(params_h, cfg, jax.device_get(toks),
                           compute_dtype=jnp.float32)[0]
        err = float(jnp.abs(lo32 - lo_ref).max())
        print(json.dumps({"fwd": list(lo.shape), "dec": list(lo1.shape),
                          "err": err}))
    """)
    assert res["fwd"] == [8, 16, 256]
    assert res["dec"] == [8, 256]
    assert res["err"] < 1e-4          # fp32 reduction-order tolerance


def test_elastic_mesh_and_resharding_restore():
    res = run_subprocess("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.manager import CheckpointManager
        from repro.distributed import sharding as SH
        from repro.distributed.elastic import (choose_mesh_shape,
                                               make_elastic_mesh)
        from repro.models import transformer as T
        from repro.models.specs import *
        attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
        cfg = ModelConfig(name="t", d_model=64, vocab=256,
                          vocab_pad_multiple=16,
                          pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),),
                          n_periods=2, scan_layers=False, remat=False)
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, params, blocking=True)
            # restore onto an 8-device mesh (as if the fleet grew)
            mesh = make_elastic_mesh(8, target_tp=2)
            shd = SH.param_shardings(mesh, cfg)
            like = jax.tree.map(jnp.zeros_like, params)
            restored = mgr.restore(like, shardings=shd)
            ok = all(bool(jnp.allclose(a, b)) for a, b in
                     zip(jax.tree.leaves(params),
                         jax.tree.leaves(jax.device_get(restored))))
        print(json.dumps({"ok": ok,
                          "shape512": choose_mesh_shape(512, 16, True),
                          "shape6": choose_mesh_shape(6, 16)}))
    """)
    assert res["ok"]
    assert res["shape512"] == [2, 16, 16]
    assert res["shape6"] == [1, 6]


def test_dryrun_smoke_cell():
    """One real dry-run cell on the full 512-device production mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-1.3b", "--shape", "decode_32k", "--no-cost-periods"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ALL DRY-RUN CELLS COMPILED" in out.stdout
