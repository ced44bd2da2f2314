"""Work of the paged-attention decode kernel
(``kernels/paged_attention``): one query token per sequence against its
cached keys and values. For a token at context length ``ctx`` a layer
reads ``ctx`` keys and values of every kv head, and does ``q . k`` and
``p . v`` for every q head: 4 * n_q * head_dim * ctx operations. The
query and the output are read and written once."""
import re

# Told apart from the block-sparse kernel's ``custom-call`` by its 4-D
# (batch, kv heads, group, head_dim) result, where no name or name
# scope says which kernel it is.
TRACE = re.compile(
    r"_decode_jit|paged_attention|"
    r"= \w+\[\d+,\d+,\d+,\d+\](\{[^}]*\})? custom-call\(")


def token(n_q: int, n_kv: int, head_dim: int, ctx: int,
          kv_bytes: int = 2, q_bytes: int = 2) -> tuple:
    """(operations, bytes) of one decode token in one layer."""
    ops = 4 * n_q * head_dim * ctx
    by = 2 * n_kv * head_dim * ctx * kv_bytes + 2 * n_q * head_dim * q_bytes
    return ops, by


def decode(structure: dict, tokens: int, ctx_sum: int) -> tuple:
    """(operations, bytes) over every layer for ``tokens`` decode tokens
    whose context lengths sum to ``ctx_sum`` (work is linear in ``ctx``,
    so the sum is enough)."""
    ops = by = 0
    for layer in structure["layers"]:
        a = (layer["n_q"], layer["n_kv"], layer["head_dim"])
        o_ctx, b_ctx = token(*a, ctx=1)
        o0, b0 = token(*a, ctx=0)
        ops += tokens * o0 + ctx_sum * (o_ctx - o0)
        by += tokens * b0 + ctx_sum * (b_ctx - b0)
    return ops, by
