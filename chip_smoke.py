"""Chip smoke test: the main path on one TPU at phi3-medium-14b widths.

    python chip_smoke.py

recipe -> ``MosaicPipeline`` -> ``PrunedArtifact`` (saved, then loaded)
-> ``ContinuousEngine`` (paged KV pool, block-sparse MLPs, paged
attention kernel) -> ``Gateway`` on an ephemeral port, all in this one
process. The model is phi3-medium-14b at its published widths, cut to
4 of its 40 layers, with bfloat16 weights drawn from a fixed seed.

The script fails (exit code 1, no result line) unless:

- the pack stage planned every MLP projection (no silent dense
  fallback);
- the compiled decode step holds Pallas kernels (``tpu_custom_call``),
  i.e. they were compiled, not interpreted;
- every gateway request ends ``done``, with the greedy tokens of
  ``ContinuousEngine.run`` on the same requests;
- sparse-kernel logits match XLA dense logits of the same pruned
  weights, and paged-kernel decode logits match the gather path;
- the block-sparse (bf16, int8) and paged-attention kernels match
  their ``ref.py`` oracles at these widths.

Its last line is ``{"ok": true, "device": {...}}``. Without a TPU it
exits non-zero at once. The phases are functions of a
:class:`SmokeConfig`, so ``tests/test_chip_smoke.py`` rehearses them on
the CPU at a tiny width.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Relative max-abs error allowed between two bfloat16 serving paths over
# 4 layers. bfloat16 keeps 8 significant bits (one ulp is 2^-8 = 3.9e-3
# relative). Paths that sum the same products in another order (kernel
# tiles vs the XLA matmul; online vs one-shot softmax) round each
# activation to bfloat16 differently by up to an ulp; about two
# roundings per projection and attention, over 4 layers, compound to a
# few ulps. 3e-2 is 8 ulps: above that, the paths compute different
# things.
LOGIT_TOL = 3e-2
# A kernel against its oracle on the same bfloat16 inputs: one output
# rounding apart, plus f32 accumulation order (tests/test_kernels.py).
KERNEL_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    arch: str = "phi3-medium-14b"
    n_periods: int = 4            # layers kept of the published 40
    widths: Optional[dict] = None  # d_model/n_q/n_kv/head_dim/d_ff/vocab
    #                                overrides (CPU rehearsal only)
    seed: int = 0
    p: float = 0.5
    block: int = 128
    align_channels: int = 128
    calib_samples: int = 4
    calib_batch: int = 2
    calib_seq: int = 128
    max_slots: int = 4
    max_seq: int = 256
    block_size: int = 16
    prefill_chunk: int = 64       # one prefill shape: chunks pad to 64
    prefix_len: int = 48          # shared system prompt of two requests
    tails: tuple = (20, 37)       # their own prompt tails
    solo_lens: tuple = (131, 9)   # the two unshared prompts
    new_tokens: int = 16


def model_config(sc: SmokeConfig):
    """The configuration at published widths (or ``sc.widths``), cut to
    ``sc.n_periods`` unrolled layers."""
    from repro.configs.registry import get_config
    from repro.models.specs import LayerSpec
    cfg = get_config(sc.arch)
    if sc.widths:
        w = sc.widths
        layer = cfg.pattern[0]
        attn = dataclasses.replace(layer.mixer, n_q=w["n_q"],
                                   n_kv=w["n_kv"], head_dim=w["head_dim"])
        mlp = dataclasses.replace(layer.ffn, d_ff=w["d_ff"])
        cfg = cfg.replace(d_model=w["d_model"], vocab=w["vocab"],
                          pattern=(LayerSpec(attn, mlp),))
    return cfg.replace(n_periods=sc.n_periods, scan_layers=False,
                       remat=False)


def build_model(sc: SmokeConfig):
    """(params, cfg): bfloat16 weights from ``sc.seed``."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    cfg = model_config(sc)
    return T.init_model(jax.random.PRNGKey(sc.seed), cfg, jnp.bfloat16), cfg


def prune(sc: SmokeConfig, params, cfg):
    """Composite wanda_block recipe through ``MosaicPipeline``; returns
    (artifact, seconds)."""
    from repro.core.pipeline import MosaicPipeline
    from repro.core.recipe import CalibrationSpec, PruneRecipe
    recipe = PruneRecipe(
        arch=sc.arch, p=sc.p, category="composite", selector="wanda_block",
        block=sc.block, align_channels=sc.align_channels,
        calibration=CalibrationSpec(n_samples=sc.calib_samples,
                                    batch_size=sc.calib_batch,
                                    seq_len=sc.calib_seq, seed=sc.seed))
    t0 = time.perf_counter()
    art = MosaicPipeline(recipe).run(params, cfg)
    return art, time.perf_counter() - t0


def mlp_skipped(art) -> list:
    """MLP projections the pack stage could not plan (served dense)."""
    return [s for s in art.report["pack"]["skipped"]
            if s["name"] in ("gate", "up", "down")]


def save_and_load(art, directory: str):
    from repro.core.artifact import PrunedArtifact
    art.save(directory)
    return PrunedArtifact.load(directory)


def serve_config(sc: SmokeConfig):
    """bfloat16 compute and cache (ServeConfig defaults), paged pool,
    fused paged-attention decode."""
    from repro.serve.config import ServeConfig
    return ServeConfig(max_slots=sc.max_slots, max_seq=sc.max_seq,
                       block_size=sc.block_size,
                       prefill_chunk=sc.prefill_chunk,
                       prefill_multiple=sc.prefill_chunk,
                       paged_kernel=True)


def build_engine(sc: SmokeConfig, art):
    from repro.serve.batching import ContinuousEngine
    return ContinuousEngine.from_artifact(art, serve_config(sc))


def make_requests(sc: SmokeConfig, vocab: int) -> tuple:
    """(wire bodies, engine Requests) for the same traffic: two prompts
    on a shared ``prefix_id`` and two unshared, of mixed lengths."""
    import numpy as np
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(sc.seed)

    def toks(n):
        return rng.integers(0, vocab, n).tolist()
    prefix = toks(sc.prefix_len)
    prompts = [(prefix + toks(t), "system") for t in sc.tails]
    prompts += [(toks(n), None) for n in sc.solo_lens]
    bodies, reqs = [], []
    for uid, (prompt, pid) in enumerate(prompts):
        body = {"tokens": prompt, "max_new_tokens": sc.new_tokens}
        if pid:
            body["prefix_id"] = pid
        bodies.append(body)
        reqs.append(Request(uid=uid, prompt=prompt,
                            max_new_tokens=sc.new_tokens, prefix_id=pid))
    return bodies, reqs


def serve_direct(eng, reqs) -> dict:
    """Greedy ``ContinuousEngine.run``: tokens per uid, the first
    request alone on a cold engine (compile included), then all."""
    import copy
    t0 = time.perf_counter()
    eng.run([copy.deepcopy(reqs[0])])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    finished, stats = eng.run([copy.deepcopy(r) for r in reqs])
    return {"tokens": {f.request.uid: list(f.tokens) for f in finished},
            "first_request_s": first_s, "run_s": time.perf_counter() - t0,
            "stats": stats}


def serve_gateway(eng, bodies) -> list:
    """POST every body concurrently to a Gateway on port 0; returns each
    request's ndjson event list, in body order."""
    from repro.serve.gateway import Gateway

    async def one(port, body):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        payload = json.dumps(body).encode()
        w.write(b"POST /generate HTTP/1.1\r\nHost: smoke\r\n"
                b"Content-Length: %d\r\n\r\n" % len(payload) + payload)
        await w.drain()
        data = await r.read()
        w.close()
        await w.wait_closed()
        return [json.loads(line) for line in
                data.partition(b"\r\n\r\n")[2].splitlines() if line.strip()]

    async def go():
        gw = await Gateway(eng, port=0).start()
        try:
            return await asyncio.gather(*[one(gw.port, b) for b in bodies])
        finally:
            await gw.close()
    return asyncio.run(go())


def gateway_failures(events_per_request, direct_tokens: dict) -> list:
    """Every request must end ``done``, stream the tokens its ``done``
    event reports, and match ``ContinuousEngine.run`` exactly."""
    bad = []
    for uid, events in enumerate(events_per_request):
        last = events[-1] if events else {"event": "none"}
        if last["event"] != "done":
            bad.append(f"request {uid} ended {last}")
            continue
        streamed = [e["token"] for e in events if e["event"] == "token"]
        if streamed != last["tokens"]:
            bad.append(f"request {uid}: stream disagrees with done")
        if last["tokens"] != direct_tokens[uid]:
            bad.append(f"request {uid}: gateway tokens {last['tokens']} "
                       f"!= engine.run {direct_tokens[uid]}")
    return bad


def decode_step_text(sc: SmokeConfig, eng) -> str:
    """The compiled text of the engine's decode-and-sample step at the
    shapes it serves."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    serve = eng.serve
    B = serve.max_slots
    pool = jax.eval_shape(lambda: T.init_paged_pool(
        eng.cfg, serve.arena_blocks, serve.block_size, serve.cache_dtype))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    return eng._decode_sample.lower(
        eng.params, pool, s((B, 1), jnp.int32), s((B,), jnp.int32),
        s((B, 2), jnp.uint32), s((B,), jnp.int32), s((B,), jnp.float32),
        s((B, serve.blocks_per_seq), jnp.int32)).compile().as_text()


def _rel_err(a, ref) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(a - ref).max() / (np.abs(ref).max() + 1e-9))


def logit_checks(sc: SmokeConfig, art) -> dict:
    """One prompt prefilled, then one greedy decode step, through the
    library's paged step functions. Returns relative max-abs errors:
    sparse kernels vs XLA dense on the same pruned weights (prefill and
    decode logits), and fused paged-attention decode vs the gather
    path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.serve.engine import make_sparse_mlp_apply
    from repro.serve.paging import (make_paged_decode_step,
                                    make_paged_prefill_step)
    cfg, serve = art.cfg, serve_config(sc)
    sparse = make_sparse_mlp_apply(art.packed)
    dt = serve.compute_dtype
    n = sc.prefill_chunk - 3
    prompt = np.random.default_rng(sc.seed + 1).integers(0, cfg.vocab, n)
    tokens = np.zeros((1, sc.prefill_chunk), np.int32)
    tokens[0, :n] = prompt
    table = jnp.arange(serve.blocks_per_seq, dtype=jnp.int32)[None]
    pool0 = T.init_paged_pool(cfg, serve.blocks_per_seq, serve.block_size,
                              serve.cache_dtype)
    out = {}
    pools, last = {}, {}
    for name, mlp in (("sparse", sparse), ("dense", None)):
        step = jax.jit(make_paged_prefill_step(cfg, dt, mlp))
        logits, pools[name] = step(art.params, pool0, jnp.asarray(tokens),
                                   table, jnp.asarray([0], jnp.int32),
                                   jnp.asarray([n], jnp.int32))
        last[name] = logits[0, :n]
    out["prefill_sparse_vs_dense"] = _rel_err(last["sparse"], last["dense"])
    nxt = jnp.argmax(last["sparse"][-1]).astype(jnp.int32).reshape(1, 1)
    lengths = jnp.asarray([n], jnp.int32)
    dec = {}
    for name, mlp, kernel in (("sparse_kernel", sparse, True),
                              ("sparse_gather", sparse, False),
                              ("dense_kernel", None, True)):
        pool = pools["sparse" if mlp is not None else "dense"]
        step = jax.jit(make_paged_decode_step(cfg, dt, mlp,
                                              paged_kernel=kernel))
        dec[name], _ = step(art.params, pool, nxt, lengths, table)
    out["decode_sparse_vs_dense"] = _rel_err(dec["sparse_kernel"],
                                             dec["dense_kernel"])
    out["decode_kernel_vs_gather"] = _rel_err(dec["sparse_kernel"],
                                              dec["sparse_gather"])
    return out


def kernel_oracles(sc: SmokeConfig, cfg, m: int = 16) -> dict:
    """The served kernels against their ``ref.py`` oracles at ``cfg``'s
    (unpruned) widths, bfloat16, half the tiles kept. Returns relative
    max-abs errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.block_sparse.ops import (block_mask_from_weight_mask,
                                                blocksparse_matmul,
                                                plan_blocks)
    from repro.kernels.block_sparse.ref import block_sparse_matmul_ref
    from repro.kernels.paged_attention.ops import paged_attention_decode
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.serve.sparse import (dequantized_weight, pack_projection,
                                    sparse_linear)
    rng = np.random.default_rng(sc.seed + 2)
    b, out = sc.block, {}
    d, ff = cfg.d_model, cfg.pattern[0].ffn.d_ff
    for K, N in ((d, ff), (ff, d)):
        keep = rng.random((K // b, N // b)) < 0.5
        mask = np.repeat(np.repeat(keep, b, 0), b, 1)
        w = jnp.asarray(np.where(mask, rng.standard_normal((K, N)) / K ** .5,
                                 0.0), jnp.bfloat16)
        x = jnp.asarray(rng.standard_normal((m, K)), jnp.bfloat16)
        counts, idx = plan_blocks(block_mask_from_weight_mask(mask, b, b))
        y = blocksparse_matmul(x, w, counts, idx, block_m=m, block_k=b,
                               block_n=b)
        ref = block_sparse_matmul_ref(x, w, jnp.asarray(keep), b, b)
        out[f"block_sparse_bf16_{K}x{N}"] = _rel_err(y, ref)
        plan = pack_projection(w, block=b, quant="int8")
        wq = jnp.asarray(dequantized_weight(plan, K), jnp.bfloat16)
        y = sparse_linear(x, wq, plan, quant="int8")
        ref = block_sparse_matmul_ref(x, wq, jnp.asarray(keep), b, b)
        out[f"block_sparse_int8_{K}x{N}"] = _rel_err(y, ref)
    attn = cfg.pattern[0].mixer
    B, bs, blocks = sc.max_slots, sc.block_size, sc.max_seq // sc.block_size
    arena = (B * blocks + 1, bs, attn.n_kv * attn.head_dim)
    q = jnp.asarray(rng.standard_normal((B, 1, attn.n_q, attn.head_dim)),
                    jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal(arena), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(arena), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(B * blocks).reshape(B, blocks),
                         jnp.int32)
    lengths = jnp.asarray(rng.integers(1, sc.max_seq + 1, B), jnp.int32)
    y = paged_attention_decode(q, k, v, tables, lengths, attn.n_kv)
    ref = paged_attention_ref(q[:, 0], k, v, tables, lengths, attn.n_kv)
    out[f"paged_attention_{attn.n_q}q{attn.n_kv}kv"] = _rel_err(y[:, 0], ref)
    return out


def peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def require_tpu():
    """The JAX devices, or exit non-zero when the first is not a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's device is "
                 f"{devices[0].platform!r}); this check runs only on a TPU")
    return devices


def main() -> None:
    devices = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    sc = SmokeConfig()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}")
    fails = []

    params, cfg = build_model(sc)
    print(f"model: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"vocab={cfg.vocab} d_ff={cfg.pattern[0].ffn.d_ff} bf16")
    art, prune_s = prune(sc, params, cfg)
    del params
    rep = art.report["pack"]
    skipped = mlp_skipped(art)
    print(f"pipeline seconds: {prune_s:.3f} (stages "
          f"{art.report['stage_seconds']})")
    print(f"pack: {rep['n_packed']} plans, MLP n_skipped={len(skipped)} "
          f"(all projections skipped: {rep['n_skipped']}), "
          f"flop_savings={rep['flop_savings']:.4f}, pruned d_ff="
          f"{art.cfg.layer(0).ffn.d_ff}, n_q={art.cfg.layer(0).mixer.n_q}")
    if skipped:
        fails.append(f"MLP projections served dense: {skipped}")

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        t0 = time.perf_counter()
        art = save_and_load(art, tmp)
        print(f"artifact save+load seconds: {time.perf_counter() - t0:.3f}")

    eng = build_engine(sc, art)
    bodies, reqs = make_requests(sc, art.cfg.vocab)
    direct = serve_direct(eng, reqs)
    print(f"first request seconds (compile included): "
          f"{direct['first_request_s']:.3f}; all {len(reqs)} requests: "
          f"{direct['run_s']:.3f}")
    events = serve_gateway(eng, bodies)
    bad = gateway_failures(events, direct["tokens"])
    fails += bad
    served = sum(len(ev[-1].get("tokens", [])) for ev in events)
    shared = [ev[-1].get("prompt_blocks_shared") for ev in events]
    print(f"gateway: {len(bodies)} requests, "
          f"{sum(ev[-1]['event'] == 'done' for ev in events)} done, "
          f"tokens served {served}, prefix blocks shared {shared} "
          f"(engine.run: {direct['stats'].prompt_blocks_shared}); "
          f"identical to engine.run: {not bad}")

    n_custom = decode_step_text(sc, eng).count("tpu_custom_call")
    print(f"decode step tpu_custom_call count: {n_custom}")
    if n_custom == 0:
        fails.append("decode step holds no tpu_custom_call")

    for name, err in {**logit_checks(sc, art),
                      **kernel_oracles(sc, cfg)}.items():
        tol = KERNEL_TOL if name.startswith(("block_", "paged_")) \
            else LOGIT_TOL
        ok = err <= tol
        print(f"{name}: rel max err {err:.6g} (tol {tol}) "
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            fails.append(f"{name} {err} > {tol}")

    print(f"peak_bytes_in_use: {peak_bytes()}")
    if fails:
        for f in fails:
            print(f"FAIL: {f}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
