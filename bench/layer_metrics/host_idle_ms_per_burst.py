"""Device idle time in the profiled part of the window while the engine
thread was inside one of its own spans other than ``engine.wait``
(``serve/batching.py``: intake, admission, prefill chunks, the
copy-on-write guard, each phase of a decode burst), per ``engine.burst``
started there: the host's share of each burst's cycle that the device
spends waiting. The split is ``bench/spans.py``'s. Milliseconds."""
from bench import spans


def read(ctx):
    prog = ctx["trace"].get("program")
    bursts = prog and prog["spans"].get(spans.BURST, {}).get("n", 0)
    if not bursts:
        return None
    spans.note(ctx, prog)
    idle = sum(s for label, s, _ in prog["idle"]
               if label.startswith("engine.") and label != "engine.wait")
    return 1e3 * idle / bursts
