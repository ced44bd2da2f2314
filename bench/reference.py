"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program. It is a float32 decoder
in ``jax.numpy`` at ``highest`` matmul precision: RMSNorm, rotary
positions (half-split, theta from the configuration), grouped-query
causal attention with optional q/k/v biases, a SwiGLU MLP and an LM
head (tied to the embedding where the configuration ties them). It
runs on the benchmark's own dense weights (``model.init_params``, drawn
again from the seed), masked by the *selection*: which heads, channels
and weights the pruning kept, in the dense model's coordinates.

The selection is the one thing read from the program. It is taken at
set-up by matching every pruned weight of the artifact against the
dense weights (``extract_selection``), and the match is itself checked:
each pruned weight that is not zero must equal the dense weight it
came from, bit for bit, and kept heads and channels must keep their
order. Every value the reference computes with is then its own.
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


def extract_selection(dense: dict, pruned: dict, block: int) -> dict:
    """What the pruning kept, in dense coordinates, per layer: packed
    keep masks of every projection and the kept-head mask of the biases.
    ``problems`` lists every way the artifact fails to be a selection
    of the dense weights (empty when it is one)."""
    problems, layers = [], []
    for i, (db, pb) in enumerate(zip(dense["blocks"], pruned["blocks"])):
        da, pa, dm, pm = db["attn"], pb["attn"], db["mlp"], pb["mlp"]
        n_q = da["q"].shape[1]
        bad, nz = _head_match(pa["q"], da["q"], pa["o"], da["o"])
        heads = []
        for j in range(bad.shape[0]):
            hs = np.nonzero(bad[j] == 0)[0]
            if nz[j] == 0 or len(hs) != 1:
                problems.append(f"layer {i}: pruned head {j} matches "
                                f"{len(hs)} dense heads")
                hs = [0]
            heads.append(int(hs[0]))
        if heads != sorted(set(heads)):
            problems.append(f"layer {i}: kept heads out of order {heads}")
        sig = [(_tile_sigs(pm["up"], block), _tile_sigs(dm["up"], block)),
               (_tile_sigs(pm["gate"], block), _tile_sigs(dm["gate"], block)),
               (_tile_sigs(pm["down"].T, block),
                _tile_sigs(dm["down"].T, block))]
        chans = _match_columns([s[0] for s in sig], [s[1] for s in sig])
        if (chans < 0).any():
            problems.append(f"layer {i}: {(chans < 0).sum()} pruned "
                            "channels match no dense channel")
            chans = np.where(chans < 0, 0, chans)
        if (np.diff(chans) <= 0).any():
            problems.append(f"layer {i}: kept channels out of order")
        ff = dm["up"].shape[1]
        checks = {"q": (pa["q"], da["q"], heads, 1),
                  "o": (pa["o"], da["o"], heads, 0),
                  "k": (pa["k"], da["k"], np.arange(da["k"].shape[1]), 1),
                  "v": (pa["v"], da["v"], np.arange(da["v"].shape[1]), 1),
                  "gate": (pm["gate"], dm["gate"], chans, 1),
                  "up": (pm["up"], dm["up"], chans, 1),
                  "down": (pm["down"], dm["down"], chans, 0)}
        masks = {}
        for name, (p, d, idx, axis) in checks.items():
            n = _count_bad(p, d, idx, axis)
            if n:
                problems.append(f"layer {i} {name}: {n} kept weights differ "
                                "from the dense weights")
            size = d.shape[axis]
            masks[name] = _scatter_mask(p, idx, axis, size)
        for name in ("q_bias", "k_bias", "v_bias"):
            if name in da:
                idx = heads if name == "q_bias" else np.arange(
                    da[name].shape[0])
                got = np.asarray(pa[name], np.float32)
                want = np.asarray(da[name], np.float32)[np.asarray(idx)]
                if got.shape != want.shape or (got != want).any():
                    problems.append(f"layer {i} {name}: differs from the "
                                    "dense bias of the kept heads")
        head_keep = np.zeros(n_q, bool)
        head_keep[heads] = True
        layers.append({"masks": masks, "heads": head_keep,
                       "n_q_kept": len(heads), "ff_kept": int(len(chans)),
                       "ff": int(ff)})
    return {"layers": layers, "problems": problems}


# ------------------------------------------------------------ reference

def _unpack(bits: np.ndarray, shape) -> jax.Array:
    n = int(np.prod(shape))
    return jnp.unpackbits(jnp.asarray(bits))[:n].reshape(shape).astype(bool)


def layer_weights(block: dict, sel: dict) -> dict:
    """One layer's float32 reference weights: dense weights where the
    selection kept them, zero elsewhere (a removed head or channel is
    all zero, which adds exactly nothing)."""
    out = {}
    for group, names in (("attn", ("q", "k", "v", "o")),
                         ("mlp", ("gate", "up", "down"))):
        for name in names:
            w = block[group][name]
            m = _unpack(sel["masks"][name], w.shape)
            out[name] = jnp.where(m, w.astype(jnp.float32), 0.0)
    heads = jnp.asarray(sel["heads"])
    for name in ("q_bias", "k_bias", "v_bias"):
        if name in block["attn"]:
            b = block["attn"][name].astype(jnp.float32)
            out[name] = jnp.where(heads[:, None], b, 0.0) \
                if name == "q_bias" else b
    out["norm1"] = block["norm1"]["scale"].astype(jnp.float32)
    out["norm2"] = block["norm2"]["scale"].astype(jnp.float32)
    return out


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


def make_layer_fn(theta: float, eps: float, q_block: int = 512):
    """``fn(w, x) -> x``: one decoder layer over one sequence ``x``
    (S, d), causal from position 0, queries in blocks of ``q_block``."""

    def layer(w, x):
        S = x.shape[0]
        h = _rms(x, w["norm1"], eps)
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
        x = x + jnp.tensordot(att, w["o"].reshape(n_q * hd, -1), axes=1)
        h = _rms(x, w["norm2"], eps)
        act = jax.nn.silu(jnp.tensordot(h, w["gate"], axes=1)) \
            * jnp.tensordot(h, w["up"], axes=1)
        return x + jnp.tensordot(act, w["down"], axes=1)

    def run(w, x):
        with jax.default_matmul_precision("highest"):
            return layer(w, x)
    return jax.jit(run)


def make_head_fn(eps: float, vocab: int):
    """``fn(norm, head, x_rows, tokens) -> gaps``: for each row, how far
    the logit of ``tokens[row]`` lies below the largest logit."""

    def run(norm, head, x, tokens):
        with jax.default_matmul_precision("highest"):
            logits = (_rms(x, norm, eps) @ head)[:, :vocab]
        got = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
        return logits.max(-1) - got
    return jax.jit(run)


def served_gaps(conf_shapes: dict, dense: dict, selection: dict,
                samples: list, pad_to: int, rows: int = 512) -> list:
    """Reference gaps of the served tokens. ``samples`` are (prompt,
    served tokens) pairs; each is run once as prompt + served[:-1],
    padded to ``pad_to``, layer by layer, in float32. Returns one array
    per sample, one gap per served token: how far the served token's
    reference logit lies below the reference's best."""
    s = conf_shapes
    layer_fn = make_layer_fn(s["theta"], s["eps"])
    head_fn = make_head_fn(s["eps"], s["vocab"])
    table = dense["embed"]["table"]
    head = (table.T if s["tie"] else dense["lm_head"]["w"]).astype(jnp.float32)
    norm = dense["final_norm"]["scale"].astype(jnp.float32)
    seqs = []
    for prompt, served in samples:
        ids = np.zeros(pad_to, np.int32)
        full = list(prompt) + list(served[:-1])
        ids[:len(full)] = full
        seqs.append(jnp.take(table, jnp.asarray(ids), 0).astype(jnp.float32))
    for block, sel in zip(dense["blocks"], selection["layers"]):
        w = layer_weights(block, sel)
        seqs = [layer_fn(w, x) for x in seqs]
        del w
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
