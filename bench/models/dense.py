"""The dense decoder of ``phi3-medium-14b`` and ``qwen2-vl-2b``, a model
module (``bench/models/__init__.py``): in every layer grouped-query
attention with optional q/k/v biases and a SwiGLU MLP, each after an
RMSNorm; an LM head tied to the embedding or not."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import seed_key
from bench.reference import (_count_bad, _head_match, _match_columns, _rms,
                             _scatter_mask, _tile_sigs, _unpack, attention,
                             embed, head_gaps, make_head_fn)


def program_config(conf: dict):
    """The program's ``ModelConfig`` for configuration file ``conf``:
    the registry architecture, unrolled, at the file's depth and
    embedding tie. Fails when a width the file states differs from the
    one the program would run."""
    from repro.configs.registry import get_config
    cfg = get_config(conf["arch"]).replace(
        n_periods=conf["num_hidden_layers"], scan_layers=False, remat=False,
        tie_embeddings=bool(conf["tie_word_embeddings"]))
    if conf.get("widths"):              # CPU rehearsals only: tiny widths
        import dataclasses
        from repro.models.specs import LayerSpec
        w = conf["widths"]
        layer = cfg.pattern[0]
        attn = dataclasses.replace(layer.mixer, n_q=w["num_attention_heads"],
                                   n_kv=w["num_key_value_heads"],
                                   head_dim=w["head_dim"])
        mlp = dataclasses.replace(layer.ffn, d_ff=w["intermediate_size"])
        cfg = cfg.replace(d_model=w["hidden_size"], vocab=w["vocab_size"],
                          pattern=(LayerSpec(attn, mlp),))
    attn, mlp = cfg.pattern[0].mixer, cfg.pattern[0].ffn
    have = {"hidden_size": cfg.d_model, "vocab_size": cfg.vocab,
            "num_attention_heads": attn.n_q,
            "num_key_value_heads": attn.n_kv, "head_dim": attn.head_dim,
            "intermediate_size": mlp.d_ff, "rope_theta": attn.rope_theta,
            "attention_bias": attn.qkv_bias}
    want = dict(conf, **conf.get("widths", {}))
    bad = {k: (want[k], v) for k, v in have.items() if want[k] != v}
    if bad:
        raise ValueError(f"program config differs from {conf['name']}: "
                         f"(file, program) {bad}")
    return cfg


def shapes(conf: dict) -> dict:
    """Sizes of the dense model as the configuration file states them
    (``widths`` override them in CPU rehearsals)."""
    c = dict(conf, **conf.get("widths", {}))
    return {"d": c["hidden_size"], "n_q": c["num_attention_heads"],
            "n_kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"],
            "bias": bool(c["attention_bias"]),
            "tie": bool(c["tie_word_embeddings"]),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
            "vocab_pad": -(-c["vocab_size"] // 256) * 256}


def init_params(conf: dict, seed: int):
    """The dense bfloat16 weights in the program's tree layout, made on
    the default device in one jitted call from ``seed``."""
    s = shapes(conf)
    d, nq, nkv, hd, ff = s["d"], s["n_q"], s["n_kv"], s["hd"], s["ff"]
    bf = jnp.bfloat16

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(bf)

    def make(key):
        keys = jax.random.split(key, s["layers"] + 2)
        tree = {"embed": {"table": normal(keys[0], (s["vocab_pad"], d),
                                          0.02)},
                "final_norm": {"scale": jnp.ones((d,), bf)}}
        if not s["tie"]:
            tree["lm_head"] = {"w": normal(keys[1], (d, s["vocab_pad"]),
                                           d ** -0.5)}
        blocks = []
        for i in range(s["layers"]):
            k = jax.random.split(keys[2 + i], 10)
            attn = {"q": normal(k[0], (d, nq, hd), d ** -0.5),
                    "k": normal(k[1], (d, nkv, hd), d ** -0.5),
                    "v": normal(k[2], (d, nkv, hd), d ** -0.5),
                    "o": normal(k[3], (nq, hd, d), (nq * hd) ** -0.5)}
            if s["bias"]:
                attn["q_bias"] = normal(k[4], (nq, hd), 0.1)
                attn["k_bias"] = normal(k[5], (nkv, hd), 0.1)
                attn["v_bias"] = normal(k[6], (nkv, hd), 0.1)
            mlp = {"gate": normal(k[7], (d, ff), d ** -0.5),
                   "up": normal(k[8], (d, ff), d ** -0.5),
                   "down": normal(k[9], (ff, d), ff ** -0.5)}
            blocks.append({"norm1": {"scale": jnp.ones((d,), bf)},
                           "attn": attn,
                           "norm2": {"scale": jnp.ones((d,), bf)},
                           "mlp": mlp})
        tree["blocks"] = blocks
        return tree

    return jax.jit(make)(jnp.asarray(seed_key(seed)))


# ------------------------------------------------------------ selection

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


def make_layer_fn(theta: float, eps: float, q_block: int = 512):
    """``fn(w, x) -> x``: one decoder layer over one sequence ``x``
    (S, d), causal from position 0, queries in blocks of ``q_block``."""

    def layer(w, x):
        x = x + attention(w, _rms(x, w["norm1"], eps), theta, q_block)
        h = _rms(x, w["norm2"], eps)
        act = jax.nn.silu(jnp.tensordot(h, w["gate"], axes=1)) \
            * jnp.tensordot(h, w["up"], axes=1)
        return x + jnp.tensordot(act, w["down"], axes=1)

    def run(w, x):
        with jax.default_matmul_precision("highest"):
            return layer(w, x)
    return jax.jit(run)


def served_gaps(conf: dict, dense: dict, selection: dict, samples: list,
                pad_to: int, rows: int = 512) -> list:
    """Reference gaps of the served tokens. ``samples`` are (prompt,
    served tokens) pairs; each is run once as prompt + served[:-1],
    padded to ``pad_to``, layer by layer, in float32. Returns one array
    per sample, one gap per served token: how far the served token's
    reference logit lies below the reference's best."""
    s = shapes(conf)
    layer_fn = make_layer_fn(s["theta"], s["eps"])
    head_fn = make_head_fn(s["eps"], s["vocab"])
    table = dense["embed"]["table"]
    head = (table.T if s["tie"] else dense["lm_head"]["w"]).astype(jnp.float32)
    norm = dense["final_norm"]["scale"].astype(jnp.float32)
    seqs = embed(table, samples, pad_to)
    for block, sel in zip(dense["blocks"], selection["layers"]):
        w = layer_weights(block, sel)
        seqs = [layer_fn(w, x) for x in seqs]
        del w
    return head_gaps(head_fn, norm, head, seqs, samples, rows)


# ------------------------------------------------------------ work counts

def structure(artifact, block: int) -> dict:
    """Per layer: attention head counts, ``d_ff`` and, per projection,
    (K, N, kept tiles or None where it is served dense); and the
    operations per token and per position of context."""
    cfg, layers = artifact.cfg, []
    for i in range(cfg.n_layers):
        spec = cfg.layer(i)
        a, f = spec.mixer, spec.ffn
        d = cfg.d_model
        shapes = {"q": (d, a.n_q * a.head_dim), "k": (d, a.n_kv * a.head_dim),
                  "v": (d, a.n_kv * a.head_dim), "o": (a.n_q * a.head_dim, d),
                  "gate": (d, f.d_ff), "up": (d, f.d_ff), "down": (f.d_ff, d)}
        proj = {}
        for name, (K, N) in shapes.items():
            plan = artifact.packed.get((i, name))
            kept = None if plan is None else int(np.asarray(plan.counts).sum())
            proj[name] = (K, N, kept)
        layers.append({"n_q": a.n_q, "n_kv": a.n_kv, "head_dim": a.head_dim,
                       "d_ff": f.d_ff, "proj": proj})
    st = {"d": cfg.d_model, "vocab": cfg.vocab, "block": block,
          "layers": layers}
    return dict(st, ops_per_token=ops_per_token(st),
                ops_per_context=ops_per_context(st))


def ops_per_token(structure: dict) -> int:
    """Operations of one token outside attention's context term: every
    projection at its served size (kept tiles where it is planned, whole
    where it is served dense) and the LM head."""
    ops = 0
    b = structure["block"]
    for layer in structure["layers"]:
        for K, N, kept in layer["proj"].values():
            ops += 2 * (kept * b * b if kept is not None else K * N)
    return ops + 2 * structure["d"] * structure["vocab"]


def ops_per_context(structure: dict) -> int:
    """Attention operations per position of context, over all layers."""
    return sum(4 * lay["n_q"] * lay["head_dim"]
               for lay in structure["layers"])
