"""Pallas TPU kernels for the paper's compute hot spots.

Whether a kernel runs compiled or through the Pallas interpreter is
decided here, once, from the backend: the CPU backend interprets, every
accelerator compiles. The serving and model paths never choose; a
kernel op's explicit ``interpret`` argument exists for the equivalence
tests.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True exactly on the CPU backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
