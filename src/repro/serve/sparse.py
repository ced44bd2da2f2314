"""Block-sparse serving path: run a Mosaic-pruned (``wanda_block`` /
composite) model's projections through the Pallas block-sparse kernel.

``pack_model`` walks the pruned projections once (the PC's Post-Pruning
Optimizer step, Fig. 6 #10), builds the per-projection block plans —
including a per-expert plan stack for every MoE expert weight — and
``sparse_apply_ffn`` executes the feed-forward with zero tiles skipped
(``sparse_apply_mlp`` for dense-MLP layers, ``sparse_apply_moe`` inside
the MoE dispatch). MoE expert matmuls default to the *grouped*
block-sparse kernel — all E experts in one launch, driven directly by
the stacked plan — with the per-expert launch loop kept as the
``group_experts=False`` fallback (and the reference in equivalence
tests). On TPU the skipped tiles are real MXU/HBM savings; on the CPU
backend the kernels run in interpret mode (``repro.kernels.
interpret_mode``; tests assert exact agreement with dense).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tree import tree_get, tree_set
from repro.core.registry import projections
from repro.kernels.block_sparse.ops import (block_mask_from_weight_mask,
                                            blocksparse_matmul,
                                            gather_kept_tiles, plan_blocks,
                                            plan_slots,
                                            quant_blocksparse_matmul)
from repro.models.specs import ModelConfig


@dataclasses.dataclass
class PackedProjection:
    """One projection's block plan. With ``quant="int8"`` the plan also
    carries the kept tiles themselves — compacted int8 storage plus the
    per-tile power-of-two scales and the slot map locating column
    ``n``'s step-``s`` tile — so the serving path never touches the
    dense weight."""
    counts: jax.Array          # (N/bn,)
    indices: jax.Array         # (N/bn, max_nnz)
    block: int
    density: float             # fraction of nonzero tiles
    quant: str = "none"        # "none" | "int8" (kept-tile storage)
    tiles: Optional[jax.Array] = None   # (T, block, block) int8
    scales: Optional[jax.Array] = None  # (N/bn, max_nnz) f32 pow2
    slots: Optional[jax.Array] = None   # (N/bn, max_nnz) int32 tile rows


@dataclasses.dataclass
class PackedExpertProjection:
    """A leading-``E`` stack of per-expert block plans for one MoE
    projection. Experts share ``max_nnz`` (each expert's index row is
    edge-padded, matching ``plan_blocks`` padding semantics — the kernel
    masks on ``counts``), so one stacked plan covers the whole expert
    group even when per-expert densities diverge.

    ``group`` selects the serving path: True (default) executes all E
    experts' matmuls in ONE grouped kernel launch straight off this
    stack; False falls back to E per-expert ``block_sparse`` launches
    through the :meth:`expert` views. ``ragged`` additionally opts
    decode-sized batches into the ragged (routed-tokens-only) kernel
    variant — the same stacked plan drives both."""
    counts: jax.Array          # (E, N/bn)
    indices: jax.Array         # (E, N/bn, max_nnz)
    block: int
    density: float             # mean nonzero-tile fraction over experts
    densities: tuple           # per-expert nonzero-tile fractions
    group: bool = True         # serve via the grouped (one-launch) kernel
    ragged: bool = False       # ragged dispatch for decode-sized batches
    quant: str = "none"        # "none" | "int8" (kept-tile storage)
    tiles: Optional[jax.Array] = None   # (T_total, block, block) int8 —
    #                                     every expert's kept tiles in one
    #                                     stacked array
    scales: Optional[jax.Array] = None  # (E, N/bn, max_nnz) f32 pow2
    slots: Optional[jax.Array] = None   # (E, N/bn, max_nnz) int32 —
    #                                     absolute rows into ``tiles``

    @property
    def n_experts(self) -> int:
        return int(self.counts.shape[0])

    def expert(self, e: int) -> PackedProjection:
        """The expert-``e`` view the block-sparse kernel consumes.
        Quantized stacks hand the *full* tile array to every view — the
        per-expert slot rows are absolute, so each view only ever
        reaches its own expert's tiles."""
        return PackedProjection(counts=self.counts[e],
                                indices=self.indices[e], block=self.block,
                                density=float(self.densities[e]),
                                quant=self.quant, tiles=self.tiles,
                                scales=(None if self.scales is None
                                        else self.scales[e]),
                                slots=(None if self.slots is None
                                       else self.slots[e]))


def _quantize_plan(w2, counts, indices, block: int) -> tuple:
    """Kept-tile int8 storage for one planned 2-D weight: gathered tiles
    quantised with pow2 per-tile scales, plus the (nN, max_nnz) slot and
    scale maps the kernel scalar-prefetches (dead steps edge-clamp with
    the slot map, so their scale entries are the clamped tile's)."""
    from repro.core.quant import quantize_tiles
    tiles = gather_kept_tiles(w2, counts, indices, block, block)
    q, tile_scales = quantize_tiles(tiles)
    slots, _ = plan_slots(counts, np.asarray(indices).shape[-1])
    scales = tile_scales[slots]
    return q, scales, slots


def pack_projection(w, block: int = 128,
                    quant: str = "none") -> Optional[PackedProjection]:
    """Build the kernel's block plan from a pruned weight. Returns None
    when the (2-D-folded) weight doesn't tile evenly. ``quant="int8"``
    additionally compacts the kept tiles into int8 storage riding the
    plan (see :class:`PackedProjection`)."""
    w2 = np.asarray(w).reshape(w.shape[0], -1)
    K, N = w2.shape
    if K % block or N % block:
        return None
    bm = block_mask_from_weight_mask(w2 != 0, block, block)
    counts, indices = plan_blocks(bm)
    p = PackedProjection(counts=counts, indices=indices, block=block,
                        density=float(bm.mean()), quant=quant)
    if quant == "int8":
        q, scales, slots = _quantize_plan(w2, counts, indices, block)
        p.tiles = jnp.asarray(q)
        p.scales = jnp.asarray(scales)
        p.slots = jnp.asarray(slots)
    return p


def pack_expert_projection(w, block: int = 128, group: bool = True,
                           ragged: bool = False, quant: str = "none"
                           ) -> Optional[PackedExpertProjection]:
    """Per-expert block plans for an ``(E, K, ...)`` MoE weight. Each
    expert's 2-D fold is planned independently; index rows are padded to
    the max ``max_nnz`` across experts so the stack is rectangular —
    exactly the layout the grouped kernel's scalar prefetch consumes.
    ``quant="int8"`` concatenates every expert's kept tiles into one
    int8 array with absolute slot rows, so the grouped/ragged kernels
    stream tile storage instead of the dense weight stack."""
    wh = np.asarray(w)
    E = wh.shape[0]
    w2 = wh.reshape(E, wh.shape[1], -1)
    K, N = w2.shape[1], w2.shape[2]
    if K % block or N % block:
        return None
    counts_e, indices_e, densities = [], [], []
    for e in range(E):
        bm = block_mask_from_weight_mask(w2[e] != 0, block, block)
        counts, indices = plan_blocks(bm)
        counts_e.append(counts)
        indices_e.append(indices)
        densities.append(float(bm.mean()))
    from repro.kernels.grouped_block_sparse.ops import stack_expert_plans
    counts, indices = stack_expert_plans(counts_e, indices_e)
    p = PackedExpertProjection(
        counts=jnp.asarray(counts), indices=jnp.asarray(indices),
        block=block, density=float(np.mean(densities)),
        densities=tuple(densities), group=group, ragged=ragged,
        quant=quant)
    if quant == "int8":
        tiles_e, scales_e, slots_e = [], [], []
        off = 0
        for e in range(E):
            q, scales, slots = _quantize_plan(w2[e], counts[e], indices[e],
                                              block)
            tiles_e.append(q)
            scales_e.append(scales)
            slots_e.append(slots + off)
            off += q.shape[0]
        p.tiles = jnp.asarray(np.concatenate(tiles_e))
        p.scales = jnp.asarray(np.stack(scales_e))
        p.slots = jnp.asarray(np.stack(slots_e))
    return p


def quant_plan_bytes(packed: dict, params=None, cfg=None) -> dict:
    """Real storage accounting for the int8 kept-tile plans: per
    projection, the int8 tile bytes + f32 scale-map bytes + int32 plan
    bytes, next to the projection's dense bytes and a bf16 dense
    reference — the ``bytes_after`` evidence the pack report and
    baseline gates consume."""
    per: dict = {}
    dense_lookup = {}
    if params is not None and cfg is not None:
        c = cfg if not cfg.scan_layers else cfg.unrolled()
        for proj in projections(c):
            dense_lookup[proj.key] = tree_get(params, proj.path)
    for key, p in packed.items():
        if getattr(p, "quant", "none") != "int8" or p.tiles is None:
            continue
        tile_bytes = int(p.tiles.size)                       # int8
        scale_bytes = int(p.scales.size) * 4
        plan_bytes = (int(p.counts.size) + int(p.indices.size)
                      + int(p.slots.size)) * 4
        row = {"tile_bytes": tile_bytes, "scale_bytes": scale_bytes,
               "plan_bytes": plan_bytes,
               "bytes": tile_bytes + scale_bytes + plan_bytes}
        w = dense_lookup.get(key)
        if w is not None:
            row["dense_bytes"] = int(w.size) * w.dtype.itemsize
            row["bf16_bytes"] = int(w.size) * 2
        per[f"{key[0]}:{key[1]}"] = row
    total = sum(r["bytes"] for r in per.values())
    dense = sum(r.get("dense_bytes", 0) for r in per.values())
    bf16 = sum(r.get("bf16_bytes", 0) for r in per.values())
    return {"per_projection": per, "total_bytes": total,
            "dense_bytes": dense, "bf16_bytes": bf16,
            "ratio_vs_bf16": (total / bf16 if bf16 else 0.0)}


def pack_model_with_report(params, cfg: ModelConfig, block: int = 128,
                           group_experts: bool = True,
                           ragged_moe: bool = False,
                           quant: str = "none") -> tuple:
    """Returns ``(packed, report)``: ``{(layer, name): PackedProjection}``
    for every tileable projection, plus a summary of what was *not*
    packed (the silent-``None`` paths), so serve-time coverage is
    auditable from the artifact report. ``quant="int8"`` packs kept-tile
    int8 storage into every plan and reports its real byte counts."""
    cfg = cfg if not cfg.scan_layers else cfg.unrolled()
    packed: dict = {}
    skipped: list = []
    packed_params = 0
    for proj in projections(cfg):
        w = tree_get(params, proj.path)
        n = int(np.prod(w.shape))
        if proj.expert_axis is not None:
            p = pack_expert_projection(w, block, group=group_experts,
                                       ragged=ragged_moe, quant=quant)
        else:
            p = pack_projection(w, block, quant=quant)
        if p is None:
            skipped.append({"layer": proj.layer, "name": proj.name,
                            "params": n, "reason": "non-tileable"})
        else:
            packed[proj.key] = p
            packed_params += n
    n_expert = sum(isinstance(p, PackedExpertProjection)
                   for p in packed.values())
    report = {
        "block": block,
        "group_experts": group_experts,
        "ragged_moe": ragged_moe,
        "quant": quant,
        "n_packed": len(packed),
        "n_expert_packed": n_expert,
        "packed_params": packed_params,
        "n_skipped": len(skipped),
        "skipped_params": sum(s["params"] for s in skipped),
        "skipped": skipped,
        "flop_savings": flop_savings(packed),
    }
    if quant == "int8":
        report["quant_bytes"] = quant_plan_bytes(packed, params, cfg)
    if skipped:
        logging.getLogger(__name__).info(
            "pack_model: skipped %d/%d projections (%d params) — %s",
            len(skipped), len(skipped) + len(packed),
            report["skipped_params"],
            ", ".join(sorted({s["reason"] for s in skipped})))
    return packed, report


def pack_model(params, cfg: ModelConfig, block: int = 128,
               group_experts: bool = True, ragged_moe: bool = False,
               quant: str = "none") -> dict:
    """{(layer, name): PackedProjection | PackedExpertProjection} for
    every tileable projection (MoE expert weights get per-expert plan
    stacks). Skipped (non-tileable) projections are logged; use
    :func:`pack_model_with_report` to get the summary programmatically."""
    packed, _ = pack_model_with_report(params, cfg, block,
                                       group_experts=group_experts,
                                       ragged_moe=ragged_moe, quant=quant)
    return packed


def dequantized_weight(p: PackedProjection, K: int) -> np.ndarray:
    """The fake-quant dense weight a quantized plan encodes: dequantised
    kept tiles scattered into zeros, (K, N) f32. Running the unquantized
    kernel (or a dense matmul) over this is the quantized kernels'
    reference path — bitwise-identical because the scales are powers of
    two."""
    assert p.quant == "int8" and p.tiles is not None
    b = p.block
    counts = np.asarray(p.counts)
    indices = np.asarray(p.indices)
    slots = np.asarray(p.slots)
    tiles = np.asarray(p.tiles, np.float32)
    scales = np.asarray(p.scales)
    w = np.zeros((K, counts.shape[0] * b), np.float32)
    for n in range(counts.shape[0]):
        for s in range(int(counts[n])):
            k = int(indices[n, s])
            w[k * b:(k + 1) * b, n * b:(n + 1) * b] = (
                tiles[slots[n, s]] * scales[n, s])
    return w


def apply_fake_quant(params, cfg: ModelConfig, packed: dict):
    """Replace every quantized projection's weight with its kept-tile
    dequantised round-trip, so the dense forward, the evaluate stage,
    and the unquantized-kernel reference path all see exactly the
    weights the int8 kernels compute with. Non-kept tiles are all-zero
    by construction of the plan, so scattering kept tiles into zeros
    loses nothing."""
    cfg = cfg if not cfg.scan_layers else cfg.unrolled()
    for proj in projections(cfg):
        p = packed.get(proj.key)
        if p is None or getattr(p, "quant", "none") != "int8":
            continue
        w = tree_get(params, proj.path)
        if isinstance(p, PackedExpertProjection):
            K = w.shape[1]
            wq = np.stack([dequantized_weight(p.expert(e), K)
                           for e in range(p.n_experts)])
        else:
            K = w.shape[0]
            wq = dequantized_weight(p, K)
        params = tree_set(params, proj.path,
                          jnp.asarray(wq.reshape(w.shape), w.dtype))
    return params


def _use_quant(plan, quant: Optional[str]) -> bool:
    """Resolve the serve-time quant override against the plan: ``None``
    follows the plan's own flag, ``"none"`` forces the dequantized
    reference path, ``"int8"`` requires kept-tile storage."""
    if quant is None:
        return getattr(plan, "quant", "none") == "int8" \
            and plan.tiles is not None
    if quant == "int8":
        if getattr(plan, "quant", "none") != "int8" or plan.tiles is None:
            raise ValueError(
                "quant='int8' requested but the plan carries no int8 "
                "kept-tile storage (pack with PruneRecipe.quant='int8')")
        return True
    return False


def sparse_linear(x, w, packed: PackedProjection,
                  quant: Optional[str] = None):
    """y = x @ w through the block-sparse kernel. x: (..., K); w: (K, N).
    Quantized plans stream their int8 kept tiles instead of ``w``
    (``quant`` overrides the plan flag: "none" forces the dense-weight
    reference path)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm = packed.block
    pad_m = (-M) % bm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    if _use_quant(packed, quant):
        y = quant_blocksparse_matmul(x2, packed.tiles, packed.counts,
                                     packed.indices, packed.slots,
                                     packed.scales, block_m=bm, block_k=bm,
                                     block_n=bm)
    else:
        y = blocksparse_matmul(x2, w.reshape(K, -1), packed.counts,
                               packed.indices, block_m=bm, block_k=bm,
                               block_n=bm)
    if pad_m:
        y = y[:M]
    return y.reshape(*lead, -1)


def sparse_apply_mlp(block_params: dict, spec, x, packed_layer: dict,
                     layer: int, quant: Optional[str] = None):
    """Feed-forward through the kernel (gate/up/down as available)."""
    from repro.models.layers import activation
    mlp = block_params["mlp"]
    dtype = x.dtype

    def lin(name, inp):
        w = mlp[name].astype(dtype)
        key = (layer, name)
        if key in packed_layer:
            return sparse_linear(inp, w, packed_layer[key], quant=quant)
        return inp @ w

    up = lin("up", x)
    if spec.gated:
        h = activation(spec.act, lin("gate", x)) * up
    else:
        h = activation(spec.act, up)
    return lin("down", h)


def grouped_sparse_linear(xs, ws, packed: PackedExpertProjection,
                          row_live=None, quant: Optional[str] = None):
    """y[e] = x[e] @ w[e] for all experts in ONE grouped kernel launch.
    xs: (E, M, K); ws: (E, K, ...) — trailing dims folded to N. Decode-
    sized slot batches keep the whole M panel resident per expert
    (``block_m=None``); prefill-sized batches fall back to tiling M by
    the plan block. ``row_live`` ((E, M) bool, optional): router
    occupancy — experts/M-blocks with no live row skip compute inside
    the launch (outputs for live rows are bitwise-unchanged)."""
    from repro.kernels.grouped_block_sparse.ops import (
        PANEL_ROWS_MAX, grouped_blocksparse_matmul,
        quant_grouped_blocksparse_matmul)
    E, M, K = xs.shape
    bm = packed.block
    # sublane alignment for the resident panel (16 covers bf16's
    # (16, 128) tile and f32's (8, 128)); plan-block alignment when M
    # is large enough to need tiling
    pad_m = (-M) % (16 if M <= PANEL_ROWS_MAX else bm)
    if pad_m:
        xs = jnp.pad(xs, ((0, 0), (0, pad_m), (0, 0)))
        if row_live is not None:
            row_live = jnp.pad(row_live, ((0, 0), (0, pad_m)))
    block_m = None if M <= PANEL_ROWS_MAX else bm
    if _use_quant(packed, quant):
        y = quant_grouped_blocksparse_matmul(
            xs, packed.tiles, packed.counts, packed.indices, packed.slots,
            packed.scales, block_m=block_m, block_k=bm, block_n=bm,
            row_live=row_live)
    else:
        y = grouped_blocksparse_matmul(xs, ws.reshape(E, K, -1),
                                       packed.counts, packed.indices,
                                       block_m=block_m, block_k=bm,
                                       block_n=bm, row_live=row_live)
    if pad_m:
        y = y[:, :M]
    return y


def ragged_sparse_linear(xp, ws, tile_expert,
                         packed: PackedExpertProjection,
                         quant: Optional[str] = None):
    """The ragged expert batch through the stacked tile plan in one
    launch. xp: (M, K) routed tokens packed into tile-aligned per-expert
    segments (M is already a multiple of the ragged tile height — the
    builder's static bound guarantees it); ws: (E, K, ...) — trailing
    dims folded to N; tile_expert: (M / RAGGED_BLOCK_ROWS,) owner map,
    -1 on dead padding tiles (skipped inside the kernel)."""
    from repro.kernels.grouped_block_sparse.ops import (
        RAGGED_BLOCK_ROWS, quant_ragged_blocksparse_matmul,
        ragged_blocksparse_matmul)
    M, K = xp.shape
    E = ws.shape[0]
    bm = packed.block
    assert M % RAGGED_BLOCK_ROWS == 0
    if _use_quant(packed, quant):
        return quant_ragged_blocksparse_matmul(
            xp, packed.tiles, packed.counts, packed.indices, packed.slots,
            packed.scales, tile_expert, block_m=RAGGED_BLOCK_ROWS,
            block_k=bm, block_n=bm)
    return ragged_blocksparse_matmul(xp, ws.reshape(E, K, -1),
                                     packed.counts, packed.indices,
                                     tile_expert,
                                     block_m=RAGGED_BLOCK_ROWS,
                                     block_k=bm, block_n=bm)


# Largest token count (B*S at the layer input) served through the
# ragged kernel: decode ticks qualify, prefill-sized batches fall back
# to the grouped capacity-slot launch (whose resident-panel layout wins
# once most experts are occupied anyway). Static per trace — selection
# never retraces on occupancy, only on batch shape like everything else.
RAGGED_TOKENS_MAX = 64


def sparse_apply_moe(block_params: dict, spec, x, packed_layer: dict,
                     layer: int, group_experts: Optional[bool] = None,
                     ragged_moe: Optional[bool] = None,
                     quant: Optional[str] = None):
    """MoE feed-forward with the expert matmuls run through the
    block-sparse kernels under the layer's per-expert plan stacks.
    Routing, dispatch, and combine are ``moe.apply_moe``'s own (shared
    code, no drift); only the expert matmuls are overridden.

    ``group_experts=None`` (default) follows the plans' own ``group``
    flag (set by the pack stage from ``PruneRecipe.group_experts``):
    True executes all E experts in one grouped kernel launch per
    projection, False loops E per-expert launches (the fallback and the
    reference in equivalence tests). The grouped launch is
    occupancy-masked: router counts are threaded in as a live-row mask
    so experts with zero routed tokens (and padded capacity slots) skip
    compute inside the launch.

    ``ragged_moe=None`` (default) follows the plans' ``ragged`` flag
    (from ``PruneRecipe.ragged_moe``). When enabled and the batch is
    decode-sized (``B*S <= RAGGED_TOKENS_MAX``), the capacity-slot
    dispatch is replaced wholesale by the ragged expert batch — only
    routed tokens are packed and the kernel's M-grid covers exactly
    them. All paths are bitwise-identical on served rows."""
    from repro.models.moe import apply_moe
    plans = [p for p in (packed_layer.get((layer, nm))
                         for nm in ("gate", "up", "down"))
             if isinstance(p, PackedExpertProjection)]
    if not plans:
        y, _ = apply_moe(block_params["moe"], spec, x)
        return y
    if group_experts is None:
        group_experts = all(p.group for p in plans)
    if ragged_moe is None:
        ragged_moe = all(p.ragged for p in plans)

    n_tokens = int(x.shape[0]) * int(x.shape[1])
    if ragged_moe and n_tokens <= RAGGED_TOKENS_MAX:
        def expert_ragged_linear(name, xp, ws, tile_expert):
            plan = packed_layer.get((layer, name))
            if isinstance(plan, PackedExpertProjection):
                return ragged_sparse_linear(xp, ws, tile_expert, plan,
                                            quant=quant)
            # no plan for this projection: per-row expert gather oracle
            from repro.kernels.grouped_block_sparse.ops import \
                RAGGED_BLOCK_ROWS
            row_e = jnp.maximum(
                jnp.repeat(tile_expert, RAGGED_BLOCK_ROWS), 0)
            return jnp.einsum("mk,mkn->mn", xp, ws[row_e])

        y, _ = apply_moe(block_params["moe"], spec, x,
                         expert_ragged_linear=expert_ragged_linear)
        return y

    if group_experts:
        def expert_group_linear(name, xs, ws, row_live):
            plan = packed_layer.get((layer, name))
            if isinstance(plan, PackedExpertProjection):
                return grouped_sparse_linear(xs, ws, plan,
                                             row_live=row_live,
                                             quant=quant)
            return jnp.einsum("emk,ekn->emn", xs, ws)

        y, _ = apply_moe(block_params["moe"], spec, x,
                         expert_group_linear=expert_group_linear)
        return y

    def expert_linear(name, e, xe, we):
        plan = packed_layer.get((layer, name))
        if isinstance(plan, PackedExpertProjection):
            return sparse_linear(xe, we, plan.expert(e), quant=quant)
        return xe @ we

    y, _ = apply_moe(block_params["moe"], spec, x,
                     expert_linear=expert_linear)
    return y


def sparse_apply_ffn(block_params: dict, spec, x, packed: dict,
                     layer: int, group_experts: Optional[bool] = None,
                     ragged_moe: Optional[bool] = None,
                     quant: Optional[str] = None):
    """Feed-forward dispatch for the serving ``mlp_apply`` hook: dense-MLP
    layers go through :func:`sparse_apply_mlp`, MoE layers through
    :func:`sparse_apply_moe` (grouped one-launch expert plans by
    default, per-expert launches with ``group_experts=False``, ragged
    decode dispatch with ``ragged_moe``). ``quant`` picks the weight
    storage the kernels stream: None follows each plan's own flag,
    "int8" requires kept-tile storage, "none" forces the dense-weight
    (dequantized reference) path."""
    from repro.models.specs import MoESpec
    if isinstance(spec, MoESpec):
        return sparse_apply_moe(block_params, spec, x, packed, layer,
                                group_experts=group_experts,
                                ragged_moe=ragged_moe, quant=quant)
    return sparse_apply_mlp(block_params, spec, x, packed, layer,
                            quant=quant)


def flop_savings(packed: dict) -> float:
    """Mean fraction of projection FLOPs the kernels skip. Expert plan
    stacks contribute one term per expert (each expert's matmul is a
    full projection's worth of capacity-slot FLOPs), not one term per
    stack — so MoE sweep/Pareto rows report real per-expert savings."""
    if not packed:
        return 0.0
    skipped = []
    for p in packed.values():
        if isinstance(p, PackedExpertProjection):
            skipped.extend(1.0 - d for d in p.densities)
        else:
            skipped.append(1.0 - p.density)
    return float(np.mean(skipped))


# ----------------------------------------------- plan (de)serialization
# The PrunedArtifact persists the block plans so serve startup rehydrates
# them instead of re-deriving from raw weights (no pack_model on the
# serve hot path).

def plans_to_host(packed: dict) -> tuple:
    """``(arrays, meta)``: flat npz-able arrays + JSON-able metadata.
    Expert plan stacks carry ``"expert": true`` plus their per-expert
    densities so :func:`plans_from_host` rebuilds the exact class."""
    arrays: dict = {}
    meta: dict = {}
    for (layer, name), p in packed.items():
        key = f"{layer}:{name}"
        arrays[key + ":counts"] = np.asarray(jax.device_get(p.counts))
        arrays[key + ":indices"] = np.asarray(jax.device_get(p.indices))
        meta[key] = {"block": p.block, "density": p.density}
        if isinstance(p, PackedExpertProjection):
            meta[key]["expert"] = True
            meta[key]["densities"] = list(p.densities)
            meta[key]["group"] = bool(p.group)
            meta[key]["ragged"] = bool(p.ragged)
        if getattr(p, "quant", "none") == "int8" and p.tiles is not None:
            meta[key]["quant"] = p.quant
            arrays[key + ":tiles"] = np.asarray(jax.device_get(p.tiles))
            arrays[key + ":scales"] = np.asarray(jax.device_get(p.scales))
            arrays[key + ":slots"] = np.asarray(jax.device_get(p.slots))
    return arrays, meta


def plans_from_host(arrays: dict, meta: dict) -> dict:
    """Inverse of :func:`plans_to_host`: rebuild the PackedProjection /
    PackedExpertProjection plans the engines consume."""
    packed: dict = {}
    for key, m in meta.items():
        layer, name = key.split(":")
        counts = jnp.asarray(arrays[key + ":counts"])
        indices = jnp.asarray(arrays[key + ":indices"])
        quant_kw: dict = {"quant": str(m.get("quant", "none"))}
        if quant_kw["quant"] == "int8":
            quant_kw["tiles"] = jnp.asarray(arrays[key + ":tiles"])
            quant_kw["scales"] = jnp.asarray(arrays[key + ":scales"])
            quant_kw["slots"] = jnp.asarray(arrays[key + ":slots"])
        if m.get("expert"):
            packed[(int(layer), name)] = PackedExpertProjection(
                counts=counts, indices=indices, block=int(m["block"]),
                density=float(m["density"]),
                densities=tuple(float(d) for d in m["densities"]),
                group=bool(m.get("group", True)),
                ragged=bool(m.get("ragged", False)), **quant_kw)
        else:
            packed[(int(layer), name)] = PackedProjection(
                counts=counts, indices=indices,
                block=int(m["block"]), density=float(m["density"]),
                **quant_kw)
    return packed
