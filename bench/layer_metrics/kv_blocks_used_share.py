"""KV blocks that hold a written position against blocks reserved
(``serve/paging.py``): the growth of the engine's ``kv.block_ticks_used``
over that of ``kv.block_ticks_reserved``, counters summed over decode
ticks, between the profiled part's open and close. What a reservation
made whole at admission leaves unused. Percent."""
USED, RESERVED = "kv.block_ticks_used", "kv.block_ticks_reserved"


def read(ctx):
    marks = ctx["trace"].get("counters")
    if not marks:
        return None
    opened, closed = marks
    reserved = closed.get(RESERVED, 0) - opened.get(RESERVED, 0)
    if reserved <= 0:
        return None
    return 100.0 * (closed.get(USED, 0) - opened.get(USED, 0)) / reserved
