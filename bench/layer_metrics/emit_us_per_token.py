"""Host time per decoded token to hand tokens out at the end of each
decode burst: the self time of the engine thread's ``engine.burst.emit``
spans that started in the profiled part of the window (the scheduler's
``decoded`` for every tick, one ``EngineBridge._emit`` per token into the
gateway's loop, the registry's updates) over the tokens they carry
(``bench/spans.py``). Microseconds."""
from bench import spans


def read(ctx):
    prog = ctx["trace"].get("program")
    row = prog and prog["spans"].get("engine.burst.emit")
    if not row or not row["args"].get("tokens"):
        return None
    spans.note(ctx, prog)
    return 1e6 * row["self_s"] / row["args"]["tokens"]
