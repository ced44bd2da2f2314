"""The benchmark's files by name, and the seed of its weights.

Each configuration's model (its program config, weights, reference,
selection and shapes) is in the model module its file names
(``bench/models``).
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
