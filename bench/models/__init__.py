"""Model modules: one per architecture, in ``<module>.py`` beside this
file, named by the ``"model"`` key of a configuration file
(``bench/configs/<name>.json``). A configuration of a new architecture
adds its module as a new file; the harness finds it by that name.

A module provides exactly these five functions:

- ``program_config(conf)``: the program's ``ModelConfig`` for the
  configuration at the file's depth. Fails on any width that the file
  and the program disagree on.
- ``init_params(conf, seed)``: the dense bfloat16 weights in the
  program's tree layout, drawn on the device in one jitted call from
  ``bench.model.seed_key(seed)``.
- ``extract_selection(dense, pruned, block)``: what the prune kept, in
  the dense model's coordinates, with ``problems``: every way the pruned
  tree fails to be a selection of the dense one (empty when it is one).
- ``served_gaps(conf, dense, selection, samples, pad_to)``: the float32
  reference at ``highest`` precision over each (prompt, served tokens)
  sample; one array per sample of how far each served token's logit
  lies below the reference's best.
- ``structure(artifact, block)``: the served model's shapes for the
  work counts of ``bench/work``: ``d``, ``vocab``, ``block``, per layer
  ``n_q``, ``n_kv``, ``head_dim`` and ``proj`` (per projection ``(K, N,
  kept tiles or None where it is served dense)``), and the two numbers
  ``mfu`` needs: ``ops_per_token`` and ``ops_per_context``.

The reference's plain building blocks (norm, rotary positions,
attention, LM head, tile signatures) are in ``bench/reference.py``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent
FUNCTIONS = ("program_config", "init_params", "extract_selection",
             "served_gaps", "structure")


def load(conf: dict):
    """The module that configuration ``conf`` names under ``"model"``."""
    where = f"bench/configs/{conf['name']}.json"
    if "model" not in conf:
        raise ValueError(f'{where} names no model module: it needs '
                         f'"model": "<module>" (bench/models/<module>.py)')
    path = HERE / f"{conf['model']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{where} names model {conf['model']!r}, "
                                f"but there is no bench/models/{path.name}")
    spec = importlib.util.spec_from_file_location(
        f"bench.models.{conf['model']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"bench/models/{path.name} lacks {missing}")
    return mod
