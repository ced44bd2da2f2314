"""Per-layer metrics: one reader per metric, in ``<metric name>.py``
beside this file, found by the metric's name in ``BENCHMARK.json``.

A reader is ``read(ctx) -> float | None``. ``ctx`` holds the run's
record: ``records`` (the load generator's per-request records),
``window`` (the measured window on ``time.monotonic``), ``recorder``
(the engine's decode bursts and prefill chunks), ``structure`` (the
served model's shapes, from its model module's ``structure``,
``bench/models``), ``serve`` (the ``ServeConfig``), ``prune_s``,
``trace`` (``bench/devtrace.py``'s reduction of the profiled
part of the window) and ``device_kind``. A reader that finds nothing to
read returns None, and the metric is left out of the line.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def read(name: str, ctx: dict):
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"({path.name})")
    spec = importlib.util.spec_from_file_location(
        f"bench.layer_metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    return None if value is None else float(value)


def done_in_window(ctx: dict) -> list:
    """Requests that ended ``done`` with their last token in the window."""
    w = ctx["window"]
    return [r for r in ctx["records"]
            if r["status"] == "done" and r["bursts"]
            and w[0] <= r["bursts"][-1][0] < w[1]]


def traced(ctx: dict, rows: list) -> list:
    """Recorder rows (``(t0, t1, ...)``) that started inside the
    profiled part of the window."""
    t0, t1 = ctx["trace"]["host_window"]
    return [r for r in rows if t0 <= r[0] < t1]


def decode_work(rows: list) -> tuple:
    """(tokens, sum of context lengths) of recorded decode bursts: a
    burst of ``ticks`` over ``n`` slots whose lengths sum to ``ctx``
    reads ``ctx + n`` positions at its first tick, ``n`` more at each
    later one."""
    tokens = sum(n * ticks for _, _, n, ticks, _ in rows)
    ctx_sum = sum(ticks * (ctx + n) + n * ticks * (ticks - 1) // 2
                  for _, _, n, ticks, ctx in rows)
    return tokens, ctx_sum


def prefill_work(rows: list) -> tuple:
    """(tokens, sum of context lengths) of recorded prefill chunks."""
    tokens = sum(k for _, _, _, k in rows)
    ctx_sum = sum(k * start + k * (k + 1) // 2 for _, _, start, k in rows)
    return tokens, ctx_sum
