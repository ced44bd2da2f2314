"""Work of one block-sparse projection call (``kernels/block_sparse``):
``rows`` activation rows times a (K, N) weight of which ``kept`` tiles
of ``block`` x ``block`` are stored. Operations: a multiply and an add
per kept weight per row. Bytes: the kept tiles once, the activations
in once and the result out once."""
import re

# A Pallas kernel runs as a ``custom-call`` op, and both kernels of the
# serving path are built from functions named ``_kernel``, so the trace
# tells them apart by their result: this one returns the (rows, N)
# product. A name or name scope that says which kernel it is wins where
# the trace carries one.
TRACE = re.compile(
    r"blocksparse_matmul|block_sparse|"
    r"= \w+\[\d+,\d+\](\{[^}]*\})? custom-call\(")


def call(rows: int, K: int, N: int, kept: int, block: int,
         w_bytes: int = 2, x_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call."""
    weights = kept * block * block
    return 2 * rows * weights, weights * w_bytes + rows * (K + N) * x_bytes
