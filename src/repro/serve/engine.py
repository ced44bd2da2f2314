"""Serving: prefill + decode steps and a batched generation engine.

``make_serve_step`` is the artifact the decode/long dry-run shapes lower:
one new token against a KV cache of S_max, cache updated in place.

Both step factories accept an optional ``mlp_apply`` override so a
Mosaic-pruned model's feed-forward runs through the Pallas block-sparse
kernel (``repro.serve.sparse``) in the serving hot loop. The
continuous-batching engine lives in ``repro.serve.batching``.

Engines are constructed from a single frozen
:class:`~repro.serve.config.ServeConfig` — the same shape for the
static and continuous engines, in-memory and ``from_artifact``. The
pre-ServeConfig kwarg constructors still work as deprecation shims.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.specs import ModelConfig
from repro.serve.config import ServeConfig


def make_sparse_mlp_apply(packed: dict,
                          group_experts: Optional[bool] = None,
                          ragged_moe: Optional[bool] = None,
                          quant: Optional[str] = None):
    """`mlp_apply` hook routing FFN layers through the block-sparse
    kernels wherever ``packed`` (from ``sparse.pack_model``) has a plan —
    dense MLPs per projection, MoE layers via their per-expert plan
    stacks: one grouped launch for all experts by default
    (``group_experts=None`` follows each plan's own ``group`` flag),
    E per-expert launches with ``group_experts=False``, and — with
    ``ragged_moe`` (None follows each plan's ``ragged`` flag) — the
    ragged routed-tokens-only dispatch at decode batch sizes.

    ``quant`` (from ``ServeConfig.quant``) picks the weight storage the
    kernels stream: None follows each plan's own flag, "int8" requires
    kept-tile int8 storage in the plans (raises up front if absent),
    "none" forces the dequantized reference path."""
    from repro.serve.sparse import sparse_apply_ffn

    if quant == "int8" and not any(
            getattr(p, "quant", "none") == "int8" and p.tiles is not None
            for p in packed.values()):
        raise ValueError(
            "ServeConfig.quant='int8' but no plan carries int8 kept-tile "
            "storage — pack with PruneRecipe.quant='int8' first")

    def mlp_apply(block_params, spec, x, layer):
        return sparse_apply_ffn(block_params, spec, x, packed, layer,
                                group_experts=group_experts,
                                ragged_moe=ragged_moe, quant=quant)
    return mlp_apply


def make_prefill_step(cfg: ModelConfig, compute_dtype=jnp.bfloat16,
                      mlp_apply=None):
    def prefill_step(params, tokens, cache, frontend_embeds=None):
        logits, cache, _ = T.forward(
            params, cfg, tokens, frontend_embeds=frontend_embeds,
            cache=cache, cache_index=jnp.zeros((), jnp.int32),
            compute_dtype=compute_dtype, mlp_apply=mlp_apply)
        return logits, cache
    return prefill_step


def make_serve_step(cfg: ModelConfig, compute_dtype=jnp.bfloat16,
                    mlp_apply=None):
    def serve_step(params, cache, tokens, cache_index):
        """tokens: (B, 1) — decode one token for every sequence.
        cache_index: scalar, or (B,) per-slot lengths (continuous)."""
        logits, cache, _ = T.forward(
            params, cfg, tokens, cache=cache, cache_index=cache_index,
            compute_dtype=compute_dtype, mlp_apply=mlp_apply)
        return logits[:, -1, :], cache
    return serve_step


def sample_token(logits: jax.Array, key, temperature: float = 0.0,
                 vocab: Optional[int] = None) -> jax.Array:
    if vocab is not None and vocab < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) >= vocab
        logits = jnp.where(mask, -1e30, logits)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1
                                  ).astype(jnp.int32)


def sample_tokens(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                  vocab: Optional[int] = None) -> jax.Array:
    """Per-row sampling with *traced* per-row temperatures and keys.

    logits: (B, V); keys: (B, 2) uint32 PRNG keys; temps: (B,) float32.
    Rows with ``temps <= 0`` are greedy (argmax); positive rows sample
    their own categorical stream. Because temperature is a traced
    vector — not a static argument — mixed-temperature batches never
    retrace the decode step.
    """
    if vocab is not None and vocab < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) >= vocab
        logits = jnp.where(mask, -1e30, logits)
    # branch-free on purpose: a lax.cond here stalls XLA CPU's async
    # dispatch pipeline and serializes the whole decode burst (~10x)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0).astype(logits.dtype)
    drawn = jax.vmap(jax.random.categorical)(keys, logits / safe_t[:, None])
    return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)


def request_key(seed: Optional[int], uid: int, run_seed: int) -> jax.Array:
    """The request's base sampling key: its own ``seed`` when set (the
    stream is then independent of batch composition and reproducible
    across runs), else a per-uid fold of the engine-run seed."""
    if seed is not None:
        return jax.random.PRNGKey(seed)
    return jax.random.fold_in(jax.random.PRNGKey(run_seed), uid)


def _legacy_serve_config(engine: str, max_slots, max_seq, compute_dtype,
                         cache_dtype, prefill_multiple,
                         group_experts) -> ServeConfig:
    """Assemble a ServeConfig from pre-redesign kwargs (deprecated)."""
    warnings.warn(
        f"{engine}(..., max_seq=, compute_dtype=, ...) kwargs are "
        "deprecated; pass a repro.serve.config.ServeConfig",
        DeprecationWarning, stacklevel=3)
    kw = dict(max_seq=max_seq, compute_dtype=compute_dtype,
              cache_dtype=cache_dtype, group_experts=group_experts)
    if max_slots is not None:
        kw["max_slots"] = max_slots
    if prefill_multiple is not None:
        kw["prefill_multiple"] = prefill_multiple
    defaults = ServeConfig()
    return ServeConfig(**{k: (v if v is not None
                              else getattr(defaults, k))
                          for k, v in kw.items()})


class Engine:
    """Minimal static-batch generation engine over the functional steps.

    ``packed`` (from ``sparse.pack_model``) routes the MLP projections
    through the block-sparse kernel — the Mosaic fast path.
    """

    def __init__(self, params, cfg: ModelConfig, serve=None,
                 max_seq: Optional[int] = None,
                 compute_dtype=None, cache_dtype=None,
                 packed: Optional[dict] = None,
                 group_experts: Optional[bool] = None):
        if isinstance(serve, int):      # legacy positional max_seq
            serve, max_seq = None, serve
        if serve is None:
            serve = _legacy_serve_config(
                "Engine", None, max_seq, compute_dtype, cache_dtype,
                None, group_experts)
        self.serve = serve
        self.params = params
        self.cfg = cfg
        self.max_seq = serve.max_seq
        self.cache_dtype = serve.cache_dtype
        mlp_apply = (make_sparse_mlp_apply(packed, serve.group_experts,
                                           serve.ragged_moe, serve.quant)
                     if packed else None)
        self.prefill_step = jax.jit(
            make_prefill_step(cfg, serve.compute_dtype, mlp_apply))
        self.serve_step = jax.jit(
            make_serve_step(cfg, serve.compute_dtype, mlp_apply))

    @classmethod
    def from_artifact(cls, artifact, serve=None, *, sparse: bool = True,
                      **kw) -> "Engine":
        """Serve a loaded :class:`~repro.core.artifact.PrunedArtifact`
        directly: params, pruned config, and (with ``sparse=True``) the
        saved block plans — no ``pack_model`` at startup. Rehydrated
        expert plan stacks keep their saved ``group`` flag, so MoE
        bundles packed for the grouped kernel serve through the
        one-launch path with zero repacking. ``serve`` is a
        :class:`ServeConfig` (an int is the deprecated ``max_seq``)."""
        packed = artifact.packed if sparse else None
        return cls(artifact.params, artifact.cfg, serve,
                   packed=packed or None, **kw)

    def generate(self, prompt_tokens, n_new: int, temperature: float = 0.0,
                 seed: int = 0):
        """prompt_tokens: (B, S0) -> (B, S0 + n_new)."""
        B, S0 = prompt_tokens.shape
        cache = T.init_cache(self.cfg, B, self.max_seq, self.cache_dtype)
        logits, cache = self.prefill_step(self.params, prompt_tokens, cache)
        key = jax.random.PRNGKey(seed)
        tok = sample_token(logits[:, -1, :], key, temperature, self.cfg.vocab)
        out = [prompt_tokens, tok[:, None]]
        for i in range(n_new - 1):
            key, sub = jax.random.split(key)
            logits, cache = self.serve_step(
                self.params, cache, tok[:, None], jnp.int32(S0 + i))
            tok = sample_token(logits, sub, temperature, self.cfg.vocab)
            out.append(tok[:, None])
        return jnp.concatenate(out, axis=1)
