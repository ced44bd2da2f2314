"""The plain reference's building blocks, and the selection helpers.

The reference imports nothing of the program. It is a float32 decoder
in ``jax.numpy`` at ``highest`` matmul precision, composed per
architecture by its model module (``bench/models/<module>.py``) from
the blocks here: RMSNorm, rotary positions (half-split), grouped-query
causal attention with optional q/k/v biases, the embedding of the
checked requests and the gaps read at the LM head. It runs on the
benchmark's own dense weights, drawn again from the seed, masked by the
*selection*: which heads, channels and weights the pruning kept, in
the dense model's coordinates.

The selection is the one thing read from the program. A model module
takes it at set-up by matching every pruned weight of the artifact
against the dense weights, with the exact tile signatures and matchers
here, and checks the match itself. Every value the reference computes
with is then its own.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TILE_SIG_SEED = 20251017            # fixed: signatures only have to differ


# ------------------------------------------------------------ selection

_R = (np.random.default_rng(TILE_SIG_SEED)
      .integers(1, 2 ** 30, (2, 4096), dtype=np.uint32) * 2 + 1)


@functools.partial(jax.jit, static_argnames=("rows",))
def _sig_device(w, r, rows):
    K, N = w.shape
    bits = jax.lax.bitcast_convert_type(w, jnp.uint16).astype(jnp.uint32)
    b = bits.reshape(K // rows, rows, N)
    return ((b * r[0][None, :, None]).sum(1, dtype=jnp.uint32),
            (b * r[1][None, :, None]).sum(1, dtype=jnp.uint32))


def _tile_sigs(w, rows: int) -> np.ndarray:
    """An exact 64-bit signature of every ``rows``-row tile of every
    column of the bfloat16 ``w`` (K, N): sums of the bit patterns times
    fixed odd multipliers in wrapping integer arithmetic, so equal tiles
    give equal signatures whatever the order of summation, and an
    all-zero tile gives 0."""
    s1, s2 = _sig_device(w, jnp.asarray(_R[:, :rows]), rows=rows)
    return (np.asarray(s1).astype(np.uint64) << np.uint64(32)) | \
        np.asarray(s2).astype(np.uint64)


def _match_columns(pruned_sigs: list, dense_sigs: list) -> np.ndarray:
    """For each pruned column, the dense column whose every kept tile it
    equals. ``*_sigs`` are lists (one per projection) of (tiles, N)
    signature arrays; a zero signature is a pruned (all-zero) tile.
    Columns that no projection locates get -1."""
    n = pruned_sigs[0].shape[1]
    found = np.full(n, -1, np.int64)
    for sp, sd in zip(pruned_sigs, dense_sigs):
        todo = np.nonzero(found < 0)[0]
        if not len(todo):
            break
        nz = sp[:, todo] != 0
        has = nz.any(0)
        t0 = np.argmax(nz, 0)
        for t in np.unique(t0[has]):
            cols = todo[has & (t0 == t)]
            order = np.argsort(sd[t], kind="stable")
            keys = sd[t][order]
            pos = np.clip(np.searchsorted(keys, sp[t, cols]), 0,
                          len(keys) - 1)
            hit = keys[pos] == sp[t, cols]
            cand = order[pos]
            ok = hit & np.all((sp[:, cols] == 0)
                              | (sp[:, cols] == sd[:, cand]), axis=0)
            found[cols[ok]] = cand[ok]
    return found


@jax.jit
def _head_bad(p_q, d_q, p_o, d_o):
    def one(args):
        pq, po = args                                   # (d, hd), (hd, d)
        bq = ((pq != 0)[:, None, :] & (pq[:, None, :] != d_q)).sum((0, 2))
        bo = ((po != 0)[None] & (po[None] != d_o)).sum((1, 2))
        return bq + bo, (pq != 0).sum() + (po != 0).sum()
    return jax.lax.map(one, (jnp.moveaxis(p_q, 1, 0), p_o))


def _head_match(p_q, d_q, p_o, d_o):
    """(n_q', n_q) count of places where pruned head j is not zero and
    differs from dense head h, over q and o together; and the count of
    nonzero weights of each pruned head."""
    bad, nz = _head_bad(p_q, d_q, p_o, d_o)
    return np.asarray(bad), np.asarray(nz)


@functools.partial(jax.jit, static_argnames=("axis", "n"))
def _scatter_bits(p, idx, axis, n):
    shape = list(p.shape)
    shape[axis] = n
    sl = [slice(None)] * p.ndim
    sl[axis] = idx
    m = jnp.zeros(shape, bool).at[tuple(sl)].set(p != 0)
    return jnp.packbits(m.reshape(-1))


def _scatter_mask(p, idx, axis: int, n: int) -> np.ndarray:
    """The dense-coordinate keep mask: ``p != 0`` placed at ``idx``
    along ``axis`` of a zero mask ``n`` long there, as packed bits on
    the host."""
    return np.asarray(_scatter_bits(p, jnp.asarray(idx, jnp.int32),
                                    axis=axis, n=n))


@functools.partial(jax.jit, static_argnames=("axis",))
def _bad(p, d, idx, axis):
    return ((p != 0) & (p != jnp.take(d, idx, axis=axis))).sum()


def _count_bad(p, d, idx, axis: int) -> int:
    """Nonzero pruned weights that differ from the dense weight at the
    matched position."""
    return int(_bad(p, d, jnp.asarray(idx, jnp.int32), axis=axis))


# ------------------------------------------------------------ reference

def _unpack(bits: np.ndarray, shape) -> jax.Array:
    n = int(np.prod(shape))
    return jnp.unpackbits(jnp.asarray(bits))[:n].reshape(shape).astype(bool)


def _rms(x, scale, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(w, h, theta: float, q_block: int = 512):
    """The attention sublayer over one normed sequence ``h`` (S, d),
    causal from position 0, queries in blocks of ``q_block``: q/k/v
    (with their biases where ``w`` has them), rotary positions, grouped
    queries, and the output projection ``o``."""
    S = h.shape[0]
    q = jnp.tensordot(h, w["q"], axes=1)
    k = jnp.tensordot(h, w["k"], axes=1)
    v = jnp.tensordot(h, w["v"], axes=1)
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    n_q, n_kv, hd = q.shape[1], k.shape[1], q.shape[2]
    g = n_q // n_kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for b0 in range(0, S, q_block):
        b1 = min(S, b0 + q_block)
        s = jnp.einsum("qhe,khe->hqk", q[b0:b1], k[:b1]) / math.sqrt(hd)
        causal = pos[:b1][None, :] <= pos[b0:b1][:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khe->qhe", p, v[:b1]))
    att = jnp.concatenate(outs, 0).reshape(S, n_q * hd)
    return jnp.tensordot(att, w["o"].reshape(n_q * hd, -1), axes=1)


def make_head_fn(eps: float, vocab: int):
    """``fn(norm, head, x_rows, tokens) -> gaps``: for each row, how far
    the logit of ``tokens[row]`` lies below the largest logit."""

    def run(norm, head, x, tokens):
        with jax.default_matmul_precision("highest"):
            logits = (_rms(x, norm, eps) @ head)[:, :vocab]
        got = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
        return logits.max(-1) - got
    return jax.jit(run)


def embed(table, samples: list, pad_to: int) -> list:
    """Each (prompt, served tokens) sample as prompt + served[:-1],
    padded to ``pad_to``, embedded in float32."""
    seqs = []
    for prompt, served in samples:
        ids = np.zeros(pad_to, np.int32)
        full = list(prompt) + list(served[:-1])
        ids[:len(full)] = full
        seqs.append(jnp.take(table, jnp.asarray(ids), 0).astype(jnp.float32))
    return seqs


def head_gaps(head_fn, norm, head, seqs: list, samples: list,
              rows: int = 512) -> list:
    """One array per sample, one gap per served token: how far the
    served token's logit lies below the best, read by ``head_fn``
    (``make_head_fn``) from the last layer's output ``seqs``."""
    out = []
    for x, (prompt, served) in zip(seqs, samples):
        n, start = len(served), len(prompt) - 1
        idx = np.zeros(-(-n // rows) * rows, np.int32)
        idx[:n] = np.arange(start, start + n)
        tok = np.zeros(len(idx), np.int32)
        tok[:n] = served
        parts = [head_fn(norm, head, x[jnp.asarray(idx[r:r + rows])],
                         jnp.asarray(tok[r:r + rows]))
                 for r in range(0, len(idx), rows)]
        out.append(np.concatenate([np.asarray(g) for g in parts])[:n])
    return out
