"""Mean active slots over ``max_slots`` per decode tick in the window
(the engine tick loop, ``serve/batching.py``), from the benchmark's
record of each decode burst. Percent."""


def read(ctx):
    w = ctx["window"]
    rows = [r for r in ctx["recorder"].bursts if w[0] <= r[0] < w[1]]
    ticks = sum(r[3] for r in rows)
    if not ticks:
        return None
    active = sum(r[2] * r[3] for r in rows)
    return 100.0 * active / ticks / ctx["serve"].max_slots
