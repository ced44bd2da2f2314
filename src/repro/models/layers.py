"""Core transformer layers: norms, RoPE, GQA attention, MLP variants.

Pure-functional: every module is an ``init_*`` returning a params dict and
an ``apply``-style function. Compute happens in ``cfg`` compute dtype
(params cast at use), accumulation in fp32 where it matters.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.axes import hint, hint_heads, model_axis_size
from repro.models.specs import AttentionSpec, MLPSpec
from repro.models.taps import tap

# Sequences longer than this use the chunked (flash-style, exact-FLOP)
# attention path; shorter use one dense softmax.
DENSE_ATTN_MAX = 2048
Q_CHUNK = 1024


# ---------------------------------------------------------------- norms

def init_norm(kind: str, dim: int, dtype=jnp.float32) -> dict:
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(params: dict, kind: str, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(dtype)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------- RoPE

def rope_embed(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def init_attention(key: jax.Array, d_model: int, spec: AttentionSpec,
                   dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(spec.n_q * spec.head_dim)
    p = {
        "q": (jax.random.normal(kq, (d_model, spec.n_q, spec.head_dim)) * s_in).astype(dtype),
        "k": (jax.random.normal(kk, (d_model, spec.n_kv, spec.head_dim)) * s_in).astype(dtype),
        "v": (jax.random.normal(kv, (d_model, spec.n_kv, spec.head_dim)) * s_in).astype(dtype),
        "o": (jax.random.normal(ko, (spec.n_q, spec.head_dim, d_model)) * s_out).astype(dtype),
    }
    if spec.qkv_bias:
        p["q_bias"] = jnp.zeros((spec.n_q, spec.head_dim), dtype)
        p["k_bias"] = jnp.zeros((spec.n_kv, spec.head_dim), dtype)
        p["v_bias"] = jnp.zeros((spec.n_kv, spec.head_dim), dtype)
    return p


def _dense_attention(q, k, v, q_positions, kv_positions, causal: bool,
                     kv_valid: Optional[jax.Array] = None):
    """q: (B,S,nq,D); k,v: (B,T,nkv,D). Returns (B,S,nq,D)."""
    B, S, nq, D = q.shape
    T, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    qg = q.reshape(B, S, nkv, group, D)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((B, S, T), bool)
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, nq, D)


def _chunked_causal_attention(q, k, v, positions):
    """Exact-FLOP causal attention for long sequences.

    Unrolled loop over query chunks; chunk i attends to the kv prefix
    [0, (i+1)*Q_CHUNK) only (static slice), so no masked-block FLOP waste.
    This is the jnp oracle path; the Pallas flash kernel is the TPU
    hot-path equivalent (repro/kernels/flash_attention).
    """
    B, S, nq, D = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    scale = 1.0 / math.sqrt(D)
    qc = Q_CHUNK
    n_chunks = (S + qc - 1) // qc
    assert S % qc == 0, f"seq {S} must be a multiple of {qc} for chunked attn"
    outs = []
    for i in range(n_chunks):
        qs = q[:, i * qc:(i + 1) * qc].reshape(B, qc, nkv, group, D)
        kv_len = (i + 1) * qc
        ks = k[:, :kv_len]
        vs = v[:, :kv_len]
        logits = jnp.einsum("bskgd,btkd->bkgst", qs, ks,
                            preferred_element_type=jnp.float32) * scale
        qpos = positions[:, i * qc:(i + 1) * qc]
        kpos = positions[:, :kv_len]
        mask = kpos[:, None, :] <= qpos[:, :, None]
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        outs.append(jnp.einsum("bkgst,btkd->bskgd", probs, vs)
                    .reshape(B, qc, nq, D))
    return jnp.concatenate(outs, axis=1)


# ------------------------------------------------------------ paged KV

def _paged_rows(block_tables: jax.Array, pos: jax.Array,
                block_size: int) -> jax.Array:
    """Physical arena rows for logical positions ``pos`` (B, S) through
    per-sequence ``block_tables`` (B, max_blocks)."""
    logical = jnp.clip(pos // block_size, 0, block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)
    return phys * block_size + pos % block_size


def paged_write(cache_kv: jax.Array, new: jax.Array,
                block_tables: jax.Array, start: jax.Array,
                n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Scatter ``new`` (B, S, n_kv, D) K/V rows into a paged arena
    ``cache_kv`` (n_blocks, block_size, n_kv * D) at logical positions
    ``start[b] + [0, S)`` of each sequence's ``block_tables`` row.

    The arena's flat ``(n_blocks * block_size, n_kv * D)`` view keeps
    its tiled layout, so the scatter updates it in place (no relayout of
    the arena around it). Rows past ``n_valid[b]`` (right-padded prefill
    positions) are redirected into the arena's last block — the reserved
    scratch block the allocator never hands out — so padding never
    corrupts a live block. jit-safe: ``start``/``n_valid`` may be traced.
    """
    nb, bs, width = cache_kv.shape
    B, S = new.shape[0], new.shape[1]
    flat = cache_kv.reshape(nb * bs, width)
    pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    rows = _paged_rows(block_tables, pos, bs)
    if n_valid is not None:
        ok = jnp.arange(S, dtype=jnp.int32)[None, :] < n_valid[:, None]
        rows = jnp.where(ok, rows, nb * bs - 1)     # scratch block
    flat = flat.at[rows.reshape(-1)].set(
        new.astype(flat.dtype).reshape(B * S, width))
    return flat.reshape(cache_kv.shape)


def paged_gather(cache_kv: jax.Array, block_tables: jax.Array,
                 n_kv: int) -> jax.Array:
    """Gather each sequence's logical KV view (B, max_blocks*block_size,
    n_kv, D) from the paged arena (n_blocks, block_size, n_kv * D) via
    its block table."""
    nb, bs, width = cache_kv.shape
    flat = cache_kv.reshape(nb * bs, width)
    B, M = block_tables.shape
    rows = (block_tables[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
    return flat[rows.reshape(B, M * bs)].reshape(B, M * bs, n_kv,
                                                 width // n_kv)


def apply_attention(params: dict, spec: AttentionSpec, x: jax.Array,
                    positions: jax.Array, cache: Optional[dict] = None,
                    cache_index: Optional[jax.Array] = None,
                    block_tables: Optional[jax.Array] = None,
                    n_valid: Optional[jax.Array] = None,
                    paged_kernel: bool = False):
    """Returns (out, new_cache). cache: {'k','v': (B, S_max, n_kv, D)},
    or a paged arena {'k','v': (n_blocks, block_size, n_kv * D)} when
    ``block_tables`` (B, max_blocks) maps each sequence's logical blocks
    onto arena blocks; ``n_valid`` (B,) masks right-padded positions of
    a padded (chunked) prefill. ``paged_kernel`` selects the fused Pallas
    decode kernel on the paged S==1 path; prefill and the default decode
    path use the gather reference."""
    dtype = x.dtype
    tap("attn_qkv", x)
    q = jnp.einsum("bsd,dhe->bshe", x, params["q"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, params["k"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, params["v"].astype(dtype))
    if spec.qkv_bias:
        q = q + params["q_bias"].astype(dtype)
        k = k + params["k_bias"].astype(dtype)
        v = v + params["v_bias"].astype(dtype)
    if spec.rope:
        q = rope_embed(q, positions, spec.rope_theta)
        k = rope_embed(k, positions, spec.rope_theta)

    # TP-friendly head layout for train/prefill: GQA groups whose kv/group
    # dims cannot shard over the model axis force head_dim-sharded
    # contractions whose *backward* all-gathers score-sized tensors.
    # Expanding kv to full heads (and zero-padding heads to a TP multiple)
    # keeps every attention collective out of the graph; padded heads are
    # sliced off before the o-projection. Decode keeps the compact GQA
    # cache layout (memory-bound; no backward).
    n_q_orig = q.shape[2]
    pad_heads = 0
    group = spec.n_q // spec.n_kv
    tp = model_axis_size()
    # Expand for compute whenever there is a real sequence dim (train +
    # prefill): the cache always stores the compact GQA layout; decode
    # (S==1) stays compact (memory-bound, no backward).
    expand = (tp > 1 and (spec.n_kv % tp or spec.n_q % tp)
              and x.shape[1] > 1)

    def _expand(kk, vv):
        if group > 1 or spec.n_kv % tp:
            kk = jnp.repeat(kk, group, axis=2)
            vv = jnp.repeat(vv, group, axis=2)
        return kk, vv

    new_cache = None
    if cache is not None and block_tables is not None:
        # paged pool: scatter this step's K/V rows through the block
        # table, then attend over the gathered logical view. The view
        # width (max_blocks * block_size) matches the contiguous pool's
        # S_max, so masked softmax sums are bitwise-identical to the
        # contiguous path — garbage rows in unwritten blocks get exact
        # zero probability (fp32 exp(-1e30 - max) underflows to 0).
        ci = jnp.asarray(cache_index, jnp.int32)
        if ci.ndim == 0:
            ci = jnp.broadcast_to(ci, (x.shape[0],))
        ck = paged_write(cache["k"], k, block_tables, ci, n_valid)
        cv = paged_write(cache["v"], v, block_tables, ci, n_valid)
        new_cache = {"k": ck, "v": cv}
        if paged_kernel and x.shape[1] == 1:
            # fused decode: the Pallas kernel walks the block table in
            # scalar memory and gathers arena blocks in-kernel — the
            # logical view below is never materialized
            from repro.kernels.paged_attention.ops import (
                paged_attention_decode)
            nv1 = (n_valid if n_valid is not None
                   else jnp.full((x.shape[0],), 1, jnp.int32))
            out = paged_attention_decode(q, ck, cv, block_tables,
                                         ci + nv1, spec.n_kv)
            tap("attn_o", out, channel_axes=(-2, -1))
            y = jnp.einsum("bshe,hed->bsd", out, params["o"].astype(dtype))
            return hint(y, "batch", "seq", "embed"), new_cache
        kview = paged_gather(ck, block_tables, spec.n_kv)
        vview = paged_gather(cv, block_tables, spec.n_kv)
        T_kv = kview.shape[1]
        kv_pos = jnp.broadcast_to(
            jnp.arange(T_kv, dtype=jnp.int32)[None, :],
            (x.shape[0], T_kv))
        nv = (n_valid if n_valid is not None
              else jnp.full((x.shape[0],), x.shape[1], jnp.int32))
        valid = kv_pos < (ci + nv)[:, None]
        out = _dense_attention(q, kview, vview, positions, kv_pos,
                               causal=spec.causal, kv_valid=valid)
        tap("attn_o", out, channel_axes=(-2, -1))
        y = jnp.einsum("bshe,hed->bsd", out, params["o"].astype(dtype))
        return hint(y, "batch", "seq", "embed"), new_cache
    if cache is not None:
        # write current step(s) at cache_index, attend over full cache.
        # cache_index is a scalar (whole batch at one offset: train-style
        # prefill/decode) or a (B,) vector (slot pool: every sequence at
        # its own length, continuous batching).
        ci = jnp.asarray(cache_index, jnp.int32)
        if ci.ndim == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, ci, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, ci, axis=1)
        else:
            def upd(c, u, i):
                return jax.lax.dynamic_update_slice_in_dim(c, u, i, axis=0)
            ck = jax.vmap(upd)(cache["k"], k, ci)
            cv = jax.vmap(upd)(cache["v"], v, ci)
        new_cache = {"k": ck, "v": cv}
        if expand:
            ck, cv = _expand(ck, cv)
            pad_heads = (-spec.n_q) % tp
            if pad_heads:
                padc = ((0, 0), (0, 0), (0, pad_heads), (0, 0))
                q, ck, cv = (jnp.pad(t, padc) for t in (q, ck, cv))
        q = hint_heads(q)
        ck = hint_heads(ck, kv=True)
        cv = hint_heads(cv, kv=True)
        if x.shape[1] > DENSE_ATTN_MAX and spec.causal:
            # long prefill: cache content == current tokens (index 0);
            # exact-FLOP chunked attention instead of a full SxT score
            # matrix over the cache
            kq = ck[:, :x.shape[1]]
            vq = cv[:, :x.shape[1]]
            out = _chunked_causal_attention(q, kq, vq, positions)
        else:
            S_max = ck.shape[1]
            kv_pos = jnp.arange(S_max, dtype=jnp.int32)[None, :]
            kv_pos = jnp.broadcast_to(kv_pos, (x.shape[0], S_max))
            valid = kv_pos < ((ci[:, None] if ci.ndim else ci) + x.shape[1])
            out = _dense_attention(q, ck, cv, positions, kv_pos,
                                   causal=spec.causal, kv_valid=valid)
    else:
        if expand:
            k, v = _expand(k, v)
            pad_heads = (-spec.n_q) % tp
            if pad_heads:
                padc = ((0, 0), (0, 0), (0, pad_heads), (0, 0))
                q, k, v = (jnp.pad(t, padc) for t in (q, k, v))
        q = hint_heads(q)
        k = hint_heads(k, kv=True)
        v = hint_heads(v, kv=True)
        if x.shape[1] > DENSE_ATTN_MAX and spec.causal:
            out = _chunked_causal_attention(q, k, v, positions)
        else:
            out = _dense_attention(q, k, v, positions, positions,
                                   causal=spec.causal)
    if pad_heads:
        out = out[:, :, :n_q_orig, :]
    tap("attn_o", out, channel_axes=(-2, -1))
    out = hint_heads(out)
    y = jnp.einsum("bshe,hed->bsd", out, params["o"].astype(dtype))
    return hint(y, "batch", "seq", "embed"), new_cache


def init_attention_cache(batch: int, s_max: int, spec: AttentionSpec,
                         dtype=jnp.bfloat16) -> dict:
    shape = (batch, s_max, spec.n_kv, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_attention_cache(n_blocks: int, block_size: int,
                               spec: AttentionSpec,
                               dtype=jnp.bfloat16) -> dict:
    """A paged KV arena: ``n_blocks`` fixed-size blocks shared by every
    sequence (the last block is the padding scratch block). Each block
    is stored ``(block_size, n_kv * D)``, the layout the paged-attention
    kernel reads, so neither it nor ``paged_write`` relayouts the arena."""
    shape = (n_blocks, block_size, spec.n_kv * spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------- MLP

def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":  # squared ReLU (Nemotron / Primer)
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {name!r}")


def init_mlp(key: jax.Array, d_model: int, spec: MLPSpec, dtype=jnp.float32) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(spec.d_ff)
    p = {
        "up": (jax.random.normal(ku, (d_model, spec.d_ff)) * s_in).astype(dtype),
        "down": (jax.random.normal(kd, (spec.d_ff, d_model)) * s_out).astype(dtype),
    }
    if spec.gated:
        p["gate"] = (jax.random.normal(kg, (d_model, spec.d_ff)) * s_in).astype(dtype)
    return p


def apply_mlp(params: dict, spec: MLPSpec, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    tap("mlp_in", x)
    up = hint(x @ params["up"].astype(dtype), "batch", "seq", "ffn")
    if spec.gated:
        gate = activation(spec.act,
                          hint(x @ params["gate"].astype(dtype),
                               "batch", "seq", "ffn"))
        h = gate * up
    else:
        h = activation(spec.act, up)
    tap("mlp_down", h)
    return hint(h @ params["down"].astype(dtype), "batch", "seq", "embed")
