"""Operation and byte counts the served model's algorithm needs, from
its own shapes and kept tiles and from the traffic: the numerators of
the rooflines and of ``mfu``. They count what the work requires, never
what an implementation happens to do, so a share reads the same work
after a kernel is replaced.

``structure(artifact)`` reads the served model's shapes once at set-up
into plain numbers that the functions here take.
"""
from __future__ import annotations

import numpy as np


def structure(artifact, block: int) -> dict:
    """Per layer: attention head counts, ``d_ff`` and, per projection,
    (K, N, kept tiles or None where it is served dense)."""
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
    return {"d": cfg.d_model, "vocab": cfg.vocab, "block": block,
            "layers": layers}
