"""Share of the profiled window in which no operation ran on the device:
1 - (union of device op intervals) / window. Percent."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
