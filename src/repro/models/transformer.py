"""Composable decoder model: embedding -> [LayerSpec...] -> norm -> LM head.

Giant configs scan over the repeating layer pattern (HLO size O(pattern));
small / structurally-pruned models unroll with per-layer parameter shapes.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.axes import hint
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.specs import (AttentionSpec, LayerSpec, ModelConfig,
                                MoESpec)


# ---------------------------------------------------------------- init

def init_block(key: jax.Array, cfg: ModelConfig, spec: LayerSpec,
               dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    p: dict[str, Any] = {"norm1": L.init_norm(cfg.norm, cfg.d_model, dtype)}
    if isinstance(spec.mixer, AttentionSpec):
        p["attn"] = L.init_attention(k1, cfg.d_model, spec.mixer, dtype)
    else:
        p["mamba"] = SSM.init_mamba(k1, cfg.d_model, spec.mixer, dtype)
    if spec.ffn is not None:
        p["norm2"] = L.init_norm(cfg.norm, cfg.d_model, dtype)
        if isinstance(spec.ffn, MoESpec):
            p["moe"] = MOE.init_moe(k2, cfg.d_model, spec.ffn, dtype)
        else:
            p["mlp"] = L.init_mlp(k2, cfg.d_model, spec.ffn, dtype)
    return p


def init_model(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 4)
    Vp, d = cfg.padded_vocab, cfg.d_model
    params: dict[str, Any] = {
        "embed": {"table": (jax.random.normal(keys[0], (Vp, d)) * 0.02).astype(dtype)},
        "final_norm": L.init_norm(cfg.norm, d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": (jax.random.normal(keys[1], (d, Vp)) * (d ** -0.5)).astype(dtype)}

    if cfg.scan_layers:
        # stacked params: leaves get a leading n_periods axis per pattern slot
        def init_period(k):
            ks = jax.random.split(k, len(cfg.pattern))
            return tuple(init_block(ks[j], cfg, spec, dtype)
                         for j, spec in enumerate(cfg.pattern))
        period_keys = jax.random.split(keys[2], cfg.n_periods)
        stacked = jax.vmap(init_period)(period_keys)
        params["blocks"] = stacked
    else:
        ks = jax.random.split(keys[2], cfg.n_layers)
        params["blocks"] = [init_block(ks[i], cfg, cfg.layer(i), dtype)
                            for i in range(cfg.n_layers)]
    return params


# ---------------------------------------------------------------- cache

def init_block_cache(batch: int, s_max: int, spec: LayerSpec,
                     dtype=jnp.bfloat16) -> dict:
    if isinstance(spec.mixer, AttentionSpec):
        return {"attn": L.init_attention_cache(batch, s_max, spec.mixer, dtype)}
    return {"mamba": SSM.init_mamba_cache(batch, spec.mixer, dtype)}


def init_cache(cfg: ModelConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16):
    if cfg.scan_layers:
        def one_period(_):
            return tuple(init_block_cache(batch, s_max, spec, dtype)
                         for spec in cfg.pattern)
        return jax.vmap(one_period)(jnp.arange(cfg.n_periods))
    return [init_block_cache(batch, s_max, cfg.layer(i), dtype)
            for i in range(cfg.n_layers)]


# ------------------------------------------------------------- slot pool

def init_cache_pool(cfg: ModelConfig, max_slots: int, max_seq: int,
                    dtype=jnp.bfloat16):
    """A fixed ``(max_slots, max_seq)`` KV pool for continuous batching.

    The pool is an ordinary cache whose batch axis is the slot axis;
    sequences are prefillled into individual slots (``write_cache_slot``)
    and decoded at per-slot offsets (vector ``cache_index`` in
    ``forward``). Per-slot length/active bookkeeping lives host-side in
    the scheduler. Unrolled configs only: the slot axis must be the
    leading axis of every cache leaf.
    """
    if cfg.scan_layers:
        raise ValueError("cache pools require an unrolled config "
                         "(cfg.replace(scan_layers=False))")
    return init_cache(cfg, max_slots, max_seq, dtype)


def init_paged_pool(cfg: ModelConfig, n_blocks: int, block_size: int,
                    dtype=jnp.bfloat16):
    """A paged KV pool: per attention layer, one ``(n_blocks + 1,
    block_size, n_kv * D)`` arena of fixed-size KV blocks shared by every
    request through per-request block tables (``block_tables`` in
    ``forward``), instead of one contiguous ``max_seq`` region per slot.
    That is the layout the paged-attention kernel reads and the one the
    steps scatter into, so a step that is given the pool donated updates
    each arena in place (an arena stored ``(.., n_kv, D)`` would be
    relaid out on TPU, several times a step).

    The extra last block (index ``n_blocks``) is the *scratch* block:
    right-padded prefill positions and inactive decode slots write there
    so padding never corrupts a live block; the host-side allocator
    (``repro.serve.paging.BlockAllocator``) never hands it out.

    Attention-only, unrolled configs: an SSM mixer's state is recurrent,
    not positional, so it has nothing to page.
    """
    if cfg.scan_layers:
        raise ValueError("paged pools require an unrolled config "
                         "(cfg.replace(scan_layers=False))")
    pool = []
    for i in range(cfg.n_layers):
        spec = cfg.layer(i)
        if not isinstance(spec.mixer, AttentionSpec):
            raise ValueError("paged KV pools support attention mixers "
                             f"only (layer {i} is {type(spec.mixer).__name__})")
        pool.append({"attn": L.init_paged_attention_cache(
            n_blocks + 1, block_size, spec.mixer, dtype)})
    return pool


def copy_pool_block(pool, src, dst):
    """Copy arena block ``src`` -> ``dst`` in every layer of a paged
    pool — the device half of copy-on-write when a writer would touch a
    block shared between requests. jit-safe (``src``/``dst`` may be
    traced)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return jax.tree.map(
        lambda leaf: jax.lax.dynamic_update_slice_in_dim(
            leaf, jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=0),
            dst, axis=0),
        pool)


def write_cache_slot(pool, row, slot):
    """Scatter a batch-1 cache ``row`` into ``pool`` at slot ``slot``.

    ``row`` is the cache produced by a B=1 prefill; every leaf's leading
    axis is the batch/slot axis. jit-safe (``slot`` may be traced).
    """
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=0),
        pool, row)


# ---------------------------------------------------------------- forward

def apply_block(block_params: dict, cfg: ModelConfig, spec: LayerSpec,
                x: jax.Array, positions: jax.Array,
                cache: Optional[dict], cache_index,
                layer: int = 0, mlp_apply=None,
                block_tables: Optional[jax.Array] = None,
                n_valid: Optional[jax.Array] = None,
                paged_kernel: bool = False):
    h = L.apply_norm(block_params["norm1"], cfg.norm, x)
    new_cache = {}
    if isinstance(spec.mixer, AttentionSpec):
        mix, nc = L.apply_attention(
            block_params["attn"], spec.mixer, h, positions,
            cache["attn"] if cache is not None else None, cache_index,
            block_tables=block_tables, n_valid=n_valid,
            paged_kernel=paged_kernel)
        if nc is not None:
            new_cache["attn"] = nc
    else:
        mix, nc = SSM.apply_mamba(
            block_params["mamba"], spec.mixer, h,
            cache["mamba"] if cache is not None else None)
        if nc is not None:
            new_cache["mamba"] = nc
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn is not None:
        h = L.apply_norm(block_params["norm2"], cfg.norm, x)
        if mlp_apply is not None:
            # serving fast path: the hook sees every FFN (MLP and MoE)
            # spec and dispatches dense-vs-sparse per projection
            y = mlp_apply(block_params, spec.ffn, h, layer)
        elif isinstance(spec.ffn, MoESpec):
            y, aux = MOE.apply_moe(block_params["moe"], spec.ffn, h)
        else:
            y = L.apply_mlp(block_params["mlp"], spec.ffn, h)
        x = x + y
    return x, (new_cache if cache is not None else None), aux


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            frontend_embeds: Optional[jax.Array] = None,
            cache=None, cache_index=None,
            compute_dtype=jnp.bfloat16, mlp_apply=None,
            block_tables: Optional[jax.Array] = None,
            n_valid: Optional[jax.Array] = None,
            paged_kernel: bool = False):
    """Returns (logits, new_cache, aux_loss).

    tokens: (B, S) int32. frontend_embeds: (B, F, d) stub embeddings that
    replace the first F token embeddings (VLM patches / audio frames).
    cache + cache_index: decode mode (tokens are the new step(s));
    cache_index is a scalar or a per-sequence (B,) vector (slot pool).
    block_tables: (B, max_blocks) int32 — ``cache`` is a paged pool
    (``init_paged_pool``) and each sequence's KV rows are scattered /
    gathered through its block-table row; ``n_valid`` (B,) masks
    right-padded positions of a padded (chunked) prefill into the
    scratch block. Unrolled configs only. ``paged_kernel`` routes paged
    S==1 steps through the fused Pallas decode kernel instead of the
    gather path.
    mlp_apply: optional ``(block_params, ffn_spec, x, layer) -> y``
    override for FFN layers (``ffn_spec`` is an ``MLPSpec`` or
    ``MoESpec``) — the serving block-sparse fast path; MoE layers run
    each expert's capacity-slot batch through its per-expert plan and
    drop the aux loss (inference-only). Unrolled configs only (the
    layer index must be static).
    """
    B, S = tokens.shape
    if mlp_apply is not None and cfg.scan_layers:
        raise ValueError("mlp_apply needs static layer indices; use an "
                         "unrolled config (scan_layers=False)")
    if block_tables is not None and cfg.scan_layers:
        raise ValueError("paged caches need an unrolled config "
                         "(scan_layers=False)")
    if positions is None:
        if cache_index is not None:
            ci = jnp.asarray(cache_index, jnp.int32)
            ci = ci[:, None] if ci.ndim else ci
            positions = ci + jnp.arange(S, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (B, S))
        else:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(compute_dtype)
    x = hint(x, "batch", "seq", "embed")
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
    if frontend_embeds is not None:
        F = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(compute_dtype), x[:, F:]],
                            axis=1)

    aux_total = jnp.zeros((), jnp.float32)

    x = hint(x, "batch", "residual_seq", "embed")
    if cfg.scan_layers:
        def period_body(carry, xs):
            xh, aux = carry
            block_params, block_cache = xs
            new_caches = []
            for j, spec in enumerate(cfg.pattern):
                cj = block_cache[j] if block_cache is not None else None
                xh, ncj, a = apply_block(block_params[j], cfg, spec, xh,
                                         positions, cj, cache_index)
                aux = aux + a
                new_caches.append(ncj)
            # SP: the scan carry (= remat-saved activation) stays
            # seq-sharded between layers when 'residual_seq' is mapped
            xh = hint(xh, "batch", "residual_seq", "embed")
            return (xh, aux), (tuple(new_caches)
                               if block_cache is not None else 0)

        body = period_body
        if cfg.remat:
            body = jax.checkpoint(
                period_body, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux_total), new_cache = jax.lax.scan(
            body, (x, aux_total), (params["blocks"], cache))
        if cache is None:
            new_cache = None
    else:
        new_cache = [] if cache is not None else None
        for i in range(cfg.n_layers):
            ci = cache[i] if cache is not None else None
            spec_i = cfg.layer(i)

            def body(bp, xh, c, spec=spec_i, layer=i):
                return apply_block(bp, cfg, spec, xh, positions, c,
                                   cache_index, layer=layer,
                                   mlp_apply=mlp_apply,
                                   block_tables=block_tables,
                                   n_valid=n_valid,
                                   paged_kernel=paged_kernel)
            if cfg.remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, nci, a = body(params["blocks"][i], x, ci)
            x = hint(x, "batch", "residual_seq", "embed")
            aux_total = aux_total + a
            if cache is not None:
                new_cache.append(nci)

    x = L.apply_norm(params["final_norm"], cfg.norm, x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["table"].astype(compute_dtype))
    else:
        logits = x @ params["lm_head"]["w"].astype(compute_dtype)
    return logits, new_cache, aux_total


# ---------------------------------------------------------------- losses

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab: Optional[int] = None) -> jax.Array:
    """Mean next-token CE. logits: (B,S,Vp) (padded vocab ok), labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    if vocab is not None and vocab < logits.shape[-1]:
        pad = logits.shape[-1] - vocab
        neg = jnp.full((pad,), -1e30, jnp.float32)
        logits = logits + jnp.concatenate(
            [jnp.zeros((vocab,), jnp.float32), neg])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss_fn(params: dict, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, frontend_embeds=None,
            compute_dtype=jnp.bfloat16, aux_weight: float = 0.01):
    logits, _, aux = forward(params, cfg, tokens,
                             frontend_embeds=frontend_embeds,
                             compute_dtype=compute_dtype)
    ce = cross_entropy(logits, labels, cfg.vocab)
    return ce + aux_weight * aux, (ce, aux)
