"""Work of one block-sparse projection call (``kernels/block_sparse``):
``rows`` activation rows times a (K, N) weight of which ``kept`` tiles
of ``block`` x ``block`` are stored. Operations: a multiply and an add
per kept weight per row. Bytes: the kept tiles once, the activations
in once and the result out once."""


def call(rows: int, K: int, N: int, kept: int, block: int,
         w_bytes: int = 2, x_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call."""
    weights = kept * block * block
    return 2 * rows * weights, weights * w_bytes + rows * (K + N) * x_bytes


def step(structure: dict, rows: int) -> tuple:
    """(operations, bytes) of every block-sparse call in one step of
    ``rows`` rows: each planned projection of each layer once."""
    ops = by = 0
    for layer in structure["layers"]:
        for K, N, kept in layer["proj"].values():
            if kept is not None:
                o, b = call(rows, K, N, kept, structure["block"])
                ops, by = ops + o, by + b
    return ops, by
