"""Operations the served model requires per token (the numerator of
``mfu``): every projection at its served size (kept tiles where it is
planned, whole where it is served dense), attention against the
token's context, and the LM head. Padding rows, inactive slots and
recomputation are not work."""
from __future__ import annotations


def per_token(structure: dict) -> int:
    """Operations of one token outside attention's context term."""
    ops = 0
    b = structure["block"]
    for layer in structure["layers"]:
        for K, N, kept in layer["proj"].values():
            ops += 2 * (kept * b * b if kept is not None else K * N)
    return ops + 2 * structure["d"] * structure["vocab"]


def attention_per_context(structure: dict) -> int:
    """Attention operations per position of context, over all layers."""
    return sum(4 * lay["n_q"] * lay["head_dim"]
               for lay in structure["layers"])


def tokens(structure: dict, n: int, ctx_sum: int) -> int:
    """Operations of ``n`` tokens whose context lengths (counting the
    token itself) sum to ``ctx_sum``."""
    return n * per_token(structure) + ctx_sum * attention_per_context(
        structure)
