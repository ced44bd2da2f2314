"""Paged-attention decode kernel benchmark: fused Pallas kernel vs the
gather path.

Since PR 6 every paged decode tick materializes each slot's logical KV
view — ``paged_gather`` copies ``(B, max_blocks*block_size, n_kv, D)``
out of the arena per layer per token, regardless of how few blocks a
sequence actually occupies. The fused kernel prefetches block tables
into scalar memory and gathers K/V blocks inside the kernel, touching
only the blocks below each sequence's length.

Kernel timings are interpret mode on CPU, so absolute tokens/s are not
TPU numbers (they ride along ungated); the reproduction targets are

- agreement: the fused kernel matches the paged reference on ragged
  GQA workloads (``kernel_agrees``), and the engine with
  ``ServeConfig.paged_kernel`` on generates token-identical outputs to
  the gather path on a prefix-shared workload (``token_identical``);
- traffic: the per-tick gathered KV bytes, modeled analytically from
  the workload's decode schedule, strictly drop — the gather path
  moves ``blocks_per_seq`` blocks per slot per tick where the fused
  kernel reads only the live ``ceil(length / block_size)`` blocks
  (``kv_bytes_reduction``, gated in ``baseline.json``).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import counters
from repro.kernels.paged_attention.ops import paged_attention_decode
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import transformer as T
from repro.models.specs import AttentionSpec, LayerSpec, MLPSpec, ModelConfig
from repro.serve.batching import ContinuousEngine
from repro.serve.config import ServeConfig
from repro.serve.scheduler import Request

AGREE_TOL = 5e-6                # fp32 flash-softmax reassociation bound


def bench_model():
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
    cfg = ModelConfig(name="paged-attn-bench", d_model=64, vocab=256,
                      vocab_pad_multiple=16,
                      pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),),
                      n_periods=2, scan_layers=False, remat=False)
    return T.init_model(jax.random.PRNGKey(0), cfg), cfg


def kernel_agreement(B=4, M=4, bs=8, n_kv=2, n_q=4, D=16, seed=3):
    """Fused kernel vs the paged reference on a shuffled arena with
    ragged lengths; returns the max abs error."""
    rng = np.random.default_rng(seed)
    nb = B * M
    q = jnp.asarray(rng.standard_normal((B, 1, n_q, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((nb + 1, bs, n_kv * D)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb + 1, bs, n_kv * D)),
                    jnp.float32)
    tables = jnp.asarray(rng.permutation(nb).reshape(B, M), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, M * bs + 1, (B,)), jnp.int32)
    out = paged_attention_decode(q, k, v, tables, lengths, n_kv,
                                 interpret=True)
    ref = paged_attention_ref(q[:, 0], k, v, tables, lengths, n_kv)[:, None]
    return float(jnp.abs(out - ref).max())


def make_workload(n_requests=6, prefix_len=11, seed=5):
    """Mixed workload: half the requests share a prompt prefix (so the
    fused path also runs over prefix-shared block tables), half are
    unique ragged prompts."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, 250, prefix_len).tolist()
    reqs = []
    for i in range(n_requests):
        if i % 2:
            prompt = prefix + [250 + i % 5]
            pid = "sys"
        else:
            prompt = rng.integers(1, 250, 5 + 3 * i).tolist()
            pid = None
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=8,
                            prefix_id=pid))
    return reqs


def decode_kv_bytes(reqs, cfg, serve, cache_dtype=jnp.float32):
    """Analytic per-workload KV read traffic of the decode loop, in
    bytes, for both paths. Machine-independent: derived from the decode
    schedule (one tick per generated token per request), not measured.
    The gather path materializes every slot's full ``blocks_per_seq``
    logical view each tick; the fused kernel reads only the blocks
    below the sequence's current length."""
    bs = serve.block_size
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if isinstance(cfg.layer(i).mixer, AttentionSpec))
    spec = next(cfg.layer(i).mixer for i in range(cfg.n_layers)
                if isinstance(cfg.layer(i).mixer, AttentionSpec))
    block_bytes = (bs * spec.n_kv * spec.head_dim * 2 * n_attn
                   * jnp.dtype(cache_dtype).itemsize)
    gather = fused = 0
    for r in reqs:
        for t in range(1, r.max_new_tokens + 1):
            length = min(len(r.prompt) + t, serve.max_seq)
            gather += serve.blocks_per_seq * block_bytes
            fused += -(-length // bs) * block_bytes
    return gather, fused


def run_engine(params, cfg, serve, reqs):
    eng = ContinuousEngine(params, cfg, serve)
    eng.run(reqs)                       # warm-up: compile
    t0 = time.perf_counter()
    finished, stats = eng.run(reqs)
    wall = time.perf_counter() - t0
    return ({f.request.uid: f.tokens for f in finished},
            stats.generated_tokens / wall)


def main(fast: bool = True):
    params, cfg = bench_model()
    reqs = make_workload(6 if fast else 12)
    gather_cfg = ServeConfig(max_slots=4, max_seq=32, block_size=8,
                             prefill_chunk=8, compute_dtype=jnp.float32,
                             cache_dtype=jnp.float32)
    fused_cfg = dataclasses.replace(gather_cfg, paged_kernel=True)

    gather_out, gather_tps = run_engine(params, cfg, gather_cfg, reqs)
    counters.reset()
    fused_out, fused_tps = run_engine(params, cfg, fused_cfg, reqs)
    # the fused engine's decode-step trace must have dispatched the
    # kernel op (the gather engine never does); this runs before
    # kernel_agreement() below on purpose — a standalone call with the
    # same shapes would warm the op's jit cache and absorb the record
    traced = float(counters.snapshot().get("paged_attention", 0))
    identical = float(gather_out == fused_out)

    err = kernel_agreement()
    agrees = float(err < AGREE_TOL)

    gather_bytes, fused_bytes = decode_kv_bytes(reqs, cfg, gather_cfg)
    ticks = sum(r.max_new_tokens for r in reqs)
    reduction = 1.0 - fused_bytes / gather_bytes

    print(f"workload: {len(reqs)} requests, {ticks} decode ticks, "
          f"block_size {gather_cfg.block_size}, "
          f"{gather_cfg.blocks_per_seq} blocks/seq")
    print(f"{'path':12s} {'tok/s':>10s} {'KV KiB/tick':>12s}")
    for name, tps, nbytes in (("gather", gather_tps, gather_bytes),
                              ("fused", fused_tps, fused_bytes)):
        print(f"{name:12s} {tps:10.1f} {nbytes / ticks / 1024:12.2f}")
    print(f"kernel max err vs ref: {err:.1e} (agrees: {bool(agrees)}); "
          f"fused==gather tokens: {bool(identical)}; "
          f"per-tick KV bytes cut {reduction:.0%}")
    if not identical:
        # hard acceptance criterion — fail the CI bench-smoke job loudly
        raise AssertionError("fused paged-attention decode diverged "
                             "from the gather path")
    return {"kernel_agrees": agrees,
            "kernel_max_err": err,
            "token_identical": identical,
            "kernel_traced": traced,
            "kv_bytes_reduction": reduction,
            "gather_kv_bytes_per_tick": gather_bytes / ticks,
            "fused_kv_bytes_per_tick": fused_bytes / ticks,
            "gather_tokens_per_s": gather_tps,
            "fused_tokens_per_s": fused_tps}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    main(fast=not args.full)
