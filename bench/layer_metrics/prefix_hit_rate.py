"""Prompt blocks mapped from the prefix cache over prompt blocks that
could be shared (``serve/paging.py``), counted as ``ServeStats`` counts
them: complete blocks before the last prompt token of requests that
carry a ``prefix_id``, over the requests that finished in the window.
Percent."""
from bench.layer_metrics import done_in_window


def read(ctx):
    bs = ctx["serve"].block_size
    rows = [r for r in done_in_window(ctx) if r["prefix_id"]]
    shareable = sum((r["prompt_len"] - 1) // bs for r in rows)
    if not shareable:
        return None
    shared = sum(r["end"]["prompt_blocks_shared"] for r in rows)
    return 100.0 * shared / shareable
