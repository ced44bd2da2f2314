"""90th percentile of the scheduler's queue wait (arrival to admission,
``done.metrics.queue_ms`` on the engine clock) over the requests that
finished in the window (``serve/scheduler.py``, ``policies.py``)."""
from bench.harness import percentile
from bench.layer_metrics import done_in_window


def read(ctx):
    waits = [r["end"]["metrics"]["queue_ms"] for r in done_in_window(ctx)]
    return percentile(waits, 90) if waits else None
