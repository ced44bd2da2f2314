"""The cell's model, made by the benchmark: its configuration file read
into the program's ``ModelConfig``, and its bfloat16 weights drawn on
the device from ``--seed`` in one jitted call.

The weights are the benchmark's own: the program receives them as its
input, and the reference (``bench/reference.py``) draws the same tree
again from the same seed after the window, so it never reads a weight
the program made.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, traffic mix or cell."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for any non-negative seed, also
    those past 32 bits."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


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
    import jax
    import jax.numpy as jnp
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

