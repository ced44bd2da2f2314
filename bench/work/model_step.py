"""Operations the served model requires (the numerator of ``mfu``),
from the two numbers its model module's ``structure`` gives:
``ops_per_token`` (every projection at its served size and the LM
head) and ``ops_per_context`` (attention per position of context, over
all layers). Padding rows, inactive slots and recomputation are not
work."""
from __future__ import annotations


def tokens(structure: dict, n: int, ctx_sum: int) -> int:
    """Operations of ``n`` tokens whose context lengths (counting the
    token itself) sum to ``ctx_sum``."""
    return n * structure["ops_per_token"] + ctx_sum * structure[
        "ops_per_context"]
