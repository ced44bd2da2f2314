"""Serving launcher: static or continuous batching, dense / pruned /
artifact-driven.

  # serve a saved PrunedArtifact: params, config, and block plans load
  # straight from disk — no ranking, pruning, or pack_model at startup
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --artifact results/pruned_gemma --engine continuous --sparse

  # or run a recipe end-to-end (prune now, then serve the result)
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --recipe recipes/golden-smoke.json --engine continuous --sparse

  # legacy flags still work (assembled into a recipe internally)
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --prune 0.5 --category composite --engine continuous --sparse

``--engine static`` runs the fixed-batch ``Engine``; ``--engine
continuous`` runs the slot-pool ``ContinuousEngine``. ``--sparse``
routes the serving MLPs through the Pallas block-sparse kernel using
the artifact's saved ``PackedProjection`` plans.

``--block-size N`` switches the continuous engine to the paged KV pool
(``--n-blocks`` sizes the arena, ``--prefill-chunk`` interleaves long
prompt prefills with decode); ``--shared-prefix`` demos prefix sharing
by giving every request one common system prompt under a shared
``prefix_id``:

  PYTHONPATH=src python -m repro.launch.serve --smoke --engine \
      continuous --block-size 16 --prefill-chunk 16 --shared-prefix

``--gateway`` starts the HTTP front door instead of running a canned
workload: ``POST /generate`` streams ndjson tokens, ``GET /metrics`` /
``GET /healthz`` expose the engine's observability (see
``docs/serving.md``). ``--scheduler`` picks the admission policy
(fifo | priority | slo) and ``--memory-budget`` sizes the slot/block
pools from the artifact's ``report.json`` instead of ``--max-slots``:

  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --artifact results/pruned --sparse --gateway --port 8080 \
      --block-size 16 --scheduler slo
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.core.artifact import PrunedArtifact
from repro.core.pipeline import MosaicPipeline
from repro.core.recipe import CalibrationSpec, PruneRecipe
from repro.data.pipeline import SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve.batching import ContinuousEngine, latency_percentiles
from repro.serve.config import ServeConfig
from repro.serve.engine import Engine
from repro.serve.scheduler import Request


def _dtype(args):
    """Smoke configs serve in float32; real widths take ServeConfig's
    bfloat16 defaults for weights, compute and cache."""
    return jnp.float32 if args.smoke else ServeConfig.compute_dtype


def _block(args) -> int:
    """Plan tile: the recipe's (128 by default, the size the chip's
    block-sparse kernel tiles); smoke configs are too narrow for it."""
    if args.sparse_block:
        return args.sparse_block
    return 16 if args.smoke else PruneRecipe.block


def _load_or_prune(args) -> tuple:
    """Returns (params, cfg, packed, label)."""
    if args.artifact:
        art = PrunedArtifact.load(args.artifact)
        print(f"loaded artifact {args.artifact}: arch={art.recipe.arch} "
              f"category={art.report.get('category')} "
              f"{len(art.packed)} saved plans")
        return art.params, art.cfg, (art.packed if args.sparse else None), \
            "artifact"

    if args.recipe or args.prune > 0:
        if args.recipe:
            recipe = PruneRecipe.load(args.recipe)
        else:
            recipe = PruneRecipe(
                arch=args.arch, p=args.prune, category=args.category,
                align_channels=8 if args.smoke else _block(args),
                block=_block(args),
                quant=("int8" if args.quant == "int8" else "none"),
                calibration=CalibrationSpec(n_samples=8, batch_size=4,
                                            seq_len=args.prompt_len))
        if not (args.sparse or args.save_artifact):
            # plans would be discarded — skip the pack stage entirely
            recipe = recipe.replace(stages=tuple(
                s for s in recipe.stages if s != "pack"))
        elif args.sparse and "pack" not in recipe.stages:
            # --sparse needs plans even if the recipe's stages omit pack;
            # insert before 'report' so pack coverage lands in the report
            stages = list(recipe.stages)
            at = stages.index("report") if "report" in stages else len(stages)
            recipe = recipe.replace(stages=tuple(
                stages[:at] + ["pack"] + stages[at:]))
        cfg = (get_smoke_config(recipe.arch) if args.smoke
               else get_config(recipe.arch))
        cfg = cfg.replace(scan_layers=False)
        params = T.init_model(jax.random.PRNGKey(0), cfg, _dtype(args))
        art = MosaicPipeline(recipe).run(params, cfg)
        if args.save_artifact:
            art.save(args.save_artifact)
            print(f"saved PrunedArtifact to {args.save_artifact}")
        print(f"pruned p={recipe.p:.0%} via "
              f"{art.report.get('category') or recipe.category or 'auto'} "
              f"in {art.report.get('pipeline_seconds', 0.0):.1f}s")
        return art.params, art.cfg, (art.packed if args.sparse else None), \
            "recipe"

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(scan_layers=False)
    params = T.init_model(jax.random.PRNGKey(0), cfg, _dtype(args))
    packed = None
    if args.sparse:
        # unpruned weights have no zero tiles, but the kernel path is
        # still exercised (plans at ~100% density)
        from repro.serve.sparse import pack_model
        packed = pack_model(params, cfg, block=_block(args)) or None
    return params, cfg, packed, "dense"


def _run_gateway(args, params, cfg, packed) -> None:
    """Serve the HTTP front door until interrupted."""
    import asyncio
    import dataclasses

    from repro.serve.gateway import Gateway, plan_placement

    group = False if args.no_group_experts else None
    ragged = True if args.ragged_moe else None
    max_seq = args.prompt_len + args.new_tokens
    if args.block_size:
        max_seq = -(-max_seq // args.block_size) * args.block_size
    if args.memory_budget:
        if not args.artifact:
            raise SystemExit("--memory-budget sizes pools from a saved "
                             "bundle's report.json; pass --artifact")
        place = plan_placement(args.artifact, args.memory_budget,
                               max_seq=max_seq, block_size=args.block_size,
                               cache_dtype=_dtype(args),
                               scheduler=args.scheduler,
                               prefill_chunk=args.prefill_chunk)
        serve_cfg = dataclasses.replace(place.serve,
                                        compute_dtype=_dtype(args),
                                        group_experts=group,
                                        ragged_moe=ragged,
                                        quant=args.quant,
                                        paged_kernel=args.paged_kernel)
        print(f"placement: weights {place.weights_bytes} B "
              f"(density {place.density:.0%}), KV "
              f"{place.kv_token_bytes} B/token -> {place.kv_tokens} "
              f"tokens, max_slots={serve_cfg.max_slots}"
              + (f", n_blocks={serve_cfg.n_blocks}"
                 if serve_cfg.paged else ""))
    else:
        serve_cfg = ServeConfig(max_slots=args.max_slots, max_seq=max_seq,
                                block_size=args.block_size,
                                n_blocks=args.n_blocks,
                                prefill_chunk=args.prefill_chunk,
                                compute_dtype=_dtype(args),
                                cache_dtype=_dtype(args),
                                group_experts=group,
                                ragged_moe=ragged,
                                quant=args.quant,
                                paged_kernel=args.paged_kernel,
                                scheduler=args.scheduler)
    eng = ContinuousEngine(params, cfg, serve_cfg, packed=packed)

    async def _serve():
        gw = await Gateway(eng, host=args.host, port=args.port,
                           temperature=args.temperature).start()
        print(f"gateway listening on http://{args.host}:{gw.port} "
              f"(scheduler={serve_cfg.scheduler}, "
              f"{'paged' if serve_cfg.paged else 'contiguous'} pool)")
        try:
            await gw.serve_forever()
        finally:
            _, stats = await gw.close()
            print(f"gateway stopped: {stats.generated_tokens} tokens, "
                  f"{stats.rejected} rejected {stats.reject_reasons}")

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass


def main() -> None:
    enable_compile_cache()
    # surface INFO logs (e.g. pack_model's skipped-projection summary)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="serve a saved PrunedArtifact bundle")
    ap.add_argument("--recipe", default=None, metavar="JSON",
                    help="run a PruneRecipe end-to-end, then serve it")
    ap.add_argument("--save-artifact", default=None, metavar="DIR",
                    help="with --recipe/--prune: save the bundle here")
    ap.add_argument("--engine", choices=["static", "continuous"],
                    default="static")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / number of requests")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--prune", type=float, default=0.0)
    ap.add_argument("--category", default="composite",
                    choices=["unstructured", "structured", "composite"])
    ap.add_argument("--sparse", action="store_true",
                    help="serve pruned MLPs through the block-sparse kernel")
    ap.add_argument("--sparse-block", type=int, default=None,
                    help="plan tile (default: the recipe's 128; 16 "
                         "with --smoke)")
    ap.add_argument("--no-group-experts", action="store_true",
                    help="fall back to one block-sparse launch per MoE "
                         "expert instead of the grouped one-launch kernel")
    ap.add_argument("--ragged-moe", action="store_true",
                    help="MoE decode ticks: pack only routed tokens into "
                         "ragged expert batches (skips empty experts) "
                         "instead of full capacity-slot batches")
    ap.add_argument("--quant", choices=["int8", "none"], default=None,
                    help="projection weight storage: int8 streams the "
                         "plans' kept-tile int8 storage (needs a bundle "
                         "packed with quant), none forces the "
                         "dequantized reference path (default: follow "
                         "plan flags)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=None, metavar="N",
                    help="continuous engine: page the KV cache into "
                         "N-token blocks (default: contiguous slots)")
    ap.add_argument("--n-blocks", type=int, default=None, metavar="K",
                    help="paged: arena size in blocks (default: enough "
                         "for max_slots full sequences)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="paged: split prompt prefill into C-token "
                         "chunks interleaved with decode ticks")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged: decode through the fused Pallas "
                         "paged-attention kernel instead of gathering "
                         "each slot's logical KV view")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged demo: prepend one shared system prompt "
                         "to every request under a common prefix_id")
    ap.add_argument("--gateway", action="store_true",
                    help="start the streaming HTTP front door instead "
                         "of running a canned workload")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="gateway port (0 = ephemeral, printed at start)")
    ap.add_argument("--scheduler", default="fifo",
                    help="admission policy: fifo | priority | slo")
    ap.add_argument("--memory-budget", type=int, default=None,
                    metavar="BYTES",
                    help="with --artifact: size max_slots/n_blocks from "
                         "the bundle's report.json for this budget "
                         "(overrides --max-slots/--n-blocks)")
    args = ap.parse_args()

    params, cfg, packed, source = _load_or_prune(args)
    corpus = SyntheticCorpus(cfg.vocab, seed=0)
    if packed:
        from repro.serve.sparse import flop_savings
        print(f"sparse fast path: {len(packed)} plans "
              f"({source}), {flop_savings(packed):.0%} projection "
              f"FLOPs skipped")

    if args.gateway:
        _run_gateway(args, params, cfg, packed)
        return

    max_seq = args.prompt_len + args.new_tokens
    group = False if args.no_group_experts else None
    ragged = True if args.ragged_moe else None
    if args.engine == "static":
        if args.block_size:
            print("note: --block-size is a continuous-engine flag; "
                  "the static engine always uses a contiguous cache")
        serve_cfg = ServeConfig(max_seq=max_seq,
                                compute_dtype=_dtype(args),
                                cache_dtype=_dtype(args),
                                group_experts=group,
                                ragged_moe=ragged,
                                quant=args.quant)
        eng = Engine(params, cfg, serve_cfg, packed=packed)
        prompt = jnp.asarray(
            corpus.batch(0, args.batch, args.prompt_len)[:, :args.prompt_len])
        t0 = time.perf_counter()
        out = eng.generate(prompt, args.new_tokens,
                           temperature=args.temperature)
        dt = time.perf_counter() - t0
        toks = args.batch * args.new_tokens
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({toks / dt:.1f} tok/s incl. compile)")
        print("sample:", out[0, -args.new_tokens:].tolist()[:16], "...")
        return

    # continuous: mixed-length requests through the slot / block pool
    rng = np.random.default_rng(0)
    shared = (corpus.batch(99, 1, args.prompt_len)[0].tolist()
              if args.shared_prefix else [])
    reqs = []
    for i in range(args.batch):
        s0 = int(rng.integers(max(args.prompt_len // 2, 1),
                              args.prompt_len + 1))
        prompt = shared + corpus.batch(i, 1, s0)[0, :s0].tolist()
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=args.new_tokens,
                            prefix_id="system" if shared else None))
    max_seq = max(len(r.prompt) for r in reqs) + args.new_tokens
    if args.block_size:
        max_seq = -(-max_seq // args.block_size) * args.block_size
    serve_cfg = ServeConfig(max_slots=args.max_slots, max_seq=max_seq,
                            block_size=args.block_size,
                            n_blocks=args.n_blocks,
                            prefill_chunk=args.prefill_chunk,
                            compute_dtype=_dtype(args),
                            cache_dtype=_dtype(args), group_experts=group,
                            ragged_moe=ragged, quant=args.quant,
                            paged_kernel=args.paged_kernel,
                            scheduler=args.scheduler)
    eng = ContinuousEngine(params, cfg, serve_cfg, packed=packed)
    finished, stats = eng.run(reqs, temperature=args.temperature)
    lat = latency_percentiles(finished)
    print(f"served {len(finished)} requests, {stats.generated_tokens} tokens "
          f"in {stats.wall_s:.2f}s ({stats.tokens_per_s:.1f} tok/s "
          f"incl. compile), slot util {stats.slot_utilization:.0%}, "
          f"p50 {lat['p50']:.0f}ms p99 {lat['p99']:.0f}ms")
    if serve_cfg.paged:
        print(f"paged: block_size={serve_cfg.block_size} "
              f"arena={serve_cfg.arena_blocks} blocks, "
              f"peak concurrency {stats.peak_concurrency}, "
              f"{stats.prefill_chunks} prefill chunks, "
              f"{stats.prompt_blocks_shared} prompt blocks shared "
              f"(hit rate {stats.prefix_hit_rate:.0%})")
    print("sample:", finished[0].tokens[:16], "...")


if __name__ == "__main__":
    main()
