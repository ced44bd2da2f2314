"""What the benchmark reads, pinned: for both configurations at the tiny
widths of ``tiny.py`` and one seed, the dense weights, the selection
the reference takes from the prune, the served model's ``structure``,
the reference's gaps for two short requests and the operations ``mfu``
counts; the kernel patterns of ``devtrace.KERNELS``; and the kernel
seconds ``devtrace.reduce`` reads from the recorded traces. The values
were taken before the model code moved into ``bench/models``, and every
one has to stay equal to the bit.

The readings run in a child process held to one CPU core: XLA's CPU
backend splits a float32 matmul's sums by its thread count, so the
reference's gaps are bit-exact only at a fixed one."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
from bench import devtrace

ROOT = tiny.ROOT
HERE = Path(__file__).resolve().parent
CONFIGS = ("phi3-medium-14b", "qwen2-vl-2b")

CHILD = r"""
import hashlib, json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
sys.path[:0] = sys.argv[1:]
import jax
import numpy as np
import tiny
from bench.work import model_step

SEED = 2 ** 31 + 77
SAMPLES = [([5, 17, 300, 42], [7, 8, 9]),
           ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [4000, 11, 12, 13, 14, 8191])]


def digest(obj):
    h = hashlib.sha256()

    def feed(o):
        if isinstance(o, dict):
            h.update(b"{")
            for k in sorted(o):
                feed(str(k))
                feed(o[k])
            h.update(b"}")
        elif isinstance(o, (list, tuple)):
            h.update(b"[")
            for v in o:
                feed(v)
            h.update(b"]")
        elif isinstance(o, (np.ndarray, jax.Array)):
            a = np.asarray(o)
            h.update(f"{a.dtype.str}{a.shape}".encode())
            h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
        else:
            h.update(repr(o).encode())
    feed(obj)
    return h.hexdigest()


def readings(config):
    from repro.core.pipeline import MosaicPipeline
    from repro.core.recipe import PruneRecipe
    spec = tiny.spec(config, "decode-long")
    conf, mod = spec["conf"], spec["model"]
    cfg = mod.program_config(conf)
    dense = mod.init_params(conf, SEED)
    leaves = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(dense)[0]}
    recipe_d = dict(conf["recipe"], arch=conf["arch"])
    recipe_d["calibration"] = dict(recipe_d["calibration"], seed=SEED)
    recipe = PruneRecipe.from_dict(recipe_d)
    art = MosaicPipeline(recipe).run(dense, cfg)
    sel = mod.extract_selection(dense, art.params, recipe.block)
    st = mod.structure(art, recipe.block)
    gaps = mod.served_gaps(conf, dense, sel, SAMPLES, pad_to=32)
    shapes = {k: v for k, v in st.items()
              if k not in ("ops_per_token", "ops_per_context")}
    return {"weights": digest(leaves),
            "selection": digest(sel["layers"]),
            "problems": sel["problems"],
            "structure": digest(shapes),
            "ops": [st["ops_per_token"], st["ops_per_context"],
                    model_step.tokens(st, 37, 5000)],
            "gaps": [[float(x) for x in g] for g in gaps]}


print(json.dumps({c: readings(c) for c in ("phi3-medium-14b",
                                           "qwen2-vl-2b")}))
"""

PINNED = {
    "phi3-medium-14b": {
        "weights": "cbc548dfae1695ac0f69f47b897a88e7"
                   "c1ce55f689a4a66dc14961e678eb99c8",
        "selection": "62153084b72aaba51c0340a5f1708cad"
                     "f6da7731596daf871a00a64b8d248415",
        "problems": [],
        "structure": "aecc761d0f06005a118fc578b66a1999"
                     "118d5ed78bc190d4a5000ee1c0b29bdc",
        "ops": [12812288, 3072, 489414656],
        "gaps": [[4.001291275024414, 2.802727460861206, 2.6669888496398926],
                 [2.655491828918457, 4.044820308685303, 5.219452857971191,
                  3.8547403812408447, 3.4152443408966064,
                  3.6003687381744385]],
    },
    "qwen2-vl-2b": {
        "weights": "c13467b2e7118d870c22ff9452f04874"
                   "a276eb354b8abdba445ad91c58640bb3",
        "selection": "11ac360ea8dc774eb96ffc214b803fcb"
                     "3607b76aa7617dda1dd9226bce48aef0",
        "problems": [],
        "structure": "810f4662f2701a964036efdb368606cd"
                     "5a5ffa42c2ce96f9afba6269ad99dcb5",
        "ops": [12910592, 2048, 487931904],
        "gaps": [[0.9438387751579285, 1.2519272565841675, 0.8799422383308411],
                 [3.0661582946777344, 2.5198144912719727, 2.0364513397216797,
                  2.889143705368042, 1.9687854051589966,
                  1.9260518550872803]],
    },
}

KERNEL_PATTERNS = [
    ("block_sparse",
     r"blocksparse_matmul|block_sparse|"
     r"= \w+\[\d+,\d+\](\{[^}]*\})? custom-call\("),
    ("paged_attention",
     r"_decode_jit|paged_attention|"
     r"= \w+\[\d+,\d+,\d+,\d+\](\{[^}]*\})? custom-call\("),
]
KERNEL_SECONDS = {
    "trace_small.json": {"block_sparse": 2e-06, "paged_attention": 1e-06},
    "trace_spans.json": {"block_sparse": 0.0, "paged_attention": 0.0},
}


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", CHILD, str(HERE), str(ROOT),
         str(ROOT / "src")], capture_output=True, text=True, timeout=600,
        env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("reading", ["weights", "selection", "problems",
                                     "structure", "ops", "gaps"])
@pytest.mark.parametrize("config", CONFIGS)
def test_reading_is_pinned(readings, config, reading):
    assert readings[config][reading] == PINNED[config][reading]


def test_kernel_patterns_are_pinned():
    assert [(k, p.pattern) for k, p in devtrace.KERNELS.items()] \
        == KERNEL_PATTERNS


@pytest.mark.parametrize("trace", sorted(KERNEL_SECONDS))
def test_reduced_kernel_seconds_are_pinned(trace):
    ev = json.loads((HERE / "data" / trace).read_text())
    assert devtrace.reduce(ev)["kernels"] == KERNEL_SECONDS[trace]
