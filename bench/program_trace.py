"""One traced run of a cell, as ``bench/run.py --trace 1`` makes it, with
the serving program's own spans and counters read in as well.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

The program marks its engine and gateway threads with spans (``engine.*``,
``gateway.*``) and keeps KV block counters in the engine's
``MetricsRegistry``. This command reads the spans into the trace's
reduction (``bench/spans.py``) and the counters where the trace opens
and closes, and reports the per-layer metrics that read them
(``METRICS``) after the cell's own. A traced run prints no end-to-end
number; this one logs them. ``bench/run.py`` does not read the program's
spans yet: its ``Profile`` would have to, as ``ProgramProfile`` does.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

# as their entries in BENCHMARK.json's ``per_layer`` would read
METRICS = [
    {"name": "host_idle_ms_per_burst", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "engine tick loop",
     "moves": "tpot_p90_ms"},
    {"name": "emit_us_per_token", "unit": "us", "better": "lower",
     "source": "device_trace", "layer": "gateway bridge",
     "moves": "tokens_per_s"},
    {"name": "kv_blocks_used_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "KV cache manager",
     "moves": "tokens_per_s"},
]


def program_profile(devtrace, spans):
    """``devtrace.Profile`` that also reads the program's spans, and the
    counters that ``counters()`` returns at the trace's open and close."""
    class ProgramProfile(devtrace.Profile):
        def __init__(self, directory, counters):
            super().__init__(directory)
            self.counters = counters
            self.marks = []

        def start(self) -> None:
            super().start()
            self.marks.append(self.counters())

        def stop(self) -> None:
            self.marks.append(self.counters())
            super().stop()

        def reduce(self) -> dict:
            files = sorted(self.dir.rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError(
                    f"the profiler wrote no trace in {self.dir}")
            ev = devtrace.events(files[-1])
            ev["spans"] = spans.events(files[-1])
            out = devtrace.reduce(ev)
            out["host_window"] = (self.t0, self.t1)
            out["program"] = spans.program(ev)
            out["counters"] = tuple(self.marks)
            shutil.rmtree(self.dir, ignore_errors=True)
            return out
    return ProgramProfile


@contextlib.contextmanager
def _reading_the_program(log):
    """Within: ``run_cell`` profiles with ``ProgramProfile`` over the
    engine it builds, and logs the window's end-to-end numbers."""
    from bench import devtrace, harness, spans
    engines = []
    profile, e2e = devtrace.Profile, harness.end_to_end
    cls = program_profile(devtrace, spans)

    def end_to_end(*a):
        out = e2e(*a)
        log(f"end to end: {json.dumps(out)}")
        return out
    devtrace.Profile = lambda d: cls(
        d, lambda: engines[-1].metrics.counters.copy())
    harness.end_to_end = end_to_end
    try:
        yield {"engine": engines.append}
    finally:
        devtrace.Profile, harness.end_to_end = profile, e2e


def run_traced(spec: dict, seed: int, seconds: float, t_start: float, *,
               cache: bool = True, log=print) -> dict:
    """``harness.run_cell`` with ``trace`` on and the program read in."""
    from bench import harness
    spec = dict(spec, per_layer=spec["per_layer"] + METRICS)
    with _reading_the_program(log) as hooks:
        return harness.run_cell(spec, seed, seconds, True, t_start,
                                cache=cache, hooks=hooks, log=log)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]   # not bench/ itself
    from bench import harness
    from bench.run import require_chips
    spec = harness.load_spec(
        args.workload, json.loads((ROOT / "BENCHMARK.json").read_text()))
    require_chips(spec["chips"])
    result = run_traced(spec, args.seed, args.seconds, T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
