"""The profiled part of a traced run, reduced to numbers.

``Profile`` starts and stops JAX's profiler around part of the window
and marks both ends with host spans, so the window is read on the
trace's own clock. ``events`` reads the ``.xplane.pb`` into plain
tuples; ``reduce`` turns those into the device's busy and idle time,
each kernel's device time, the jitted steps the device ran, and the
breakdown of the result line. ``reduce`` is pure Python over the
tuples, so ``tests/bench`` checks it on a small recorded trace.
"""
from __future__ import annotations

import importlib
import re
import shutil
import time
from pathlib import Path

OPEN, CLOSE = "bench.trace_open", "bench.trace_close"


def _kernel_patterns() -> dict:
    """``TRACE`` of each ``bench/work`` module that declares one, by
    module name in sorted order: the first that an op matches names its
    kernel."""
    out = {}
    for path in sorted((Path(__file__).resolve().parent / "work")
                       .glob("[!_]*.py")):
        mod = importlib.import_module(f"bench.work.{path.stem}")
        if hasattr(mod, "TRACE"):
            out[path.stem] = mod.TRACE
    return out


KERNELS = _kernel_patterns()

# The engine's jitted steps, by the name XLA gives their module.
STEPS = {
    "decode": re.compile(r"decode_sample"),
    "prefill": re.compile(r"prefill_step"),
}


class Profile:
    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(str(self.dir))
        self.t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(OPEN):
            pass

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(CLOSE):
            pass
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace in {self.dir}")
        out = reduce(events(files[-1]))
        out["host_window"] = (self.t0, self.t1)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def _label(name: str, stats: dict) -> str:
    """What an event is, for matching: its name and every text stat."""
    return " ".join([name] + [str(v) for v in stats.values()])


def events(path) -> dict:
    """The trace as plain tuples: ``ops`` (device, name, start_ns, dur_ns,
    label) of every device op, ``modules`` (device, name, start_ns,
    dur_ns) of every XLA module run, and ``host`` (thread, name,
    start_ns, dur_ns) of the benchmark's own host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        st = dict(ev.stats)
                        ops.append((plane.name, ev.name, ev.start_ns,
                                    ev.duration_ns, _label(ev.name, st)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((plane.name, ev.name, ev.start_ns,
                                        ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((line.name, ev.name, ev.start_ns,
                                     ev.duration_ns))
    return {"ops": ops, "modules": modules, "host": host}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(ev: dict) -> dict:
    """Busy and idle time, kernel time and steps inside the window the
    host marked (all on the trace's clock, nanoseconds in, seconds
    out)."""
    marks = {name: start for _, name, start, _ in ev["host"]
             if name in (OPEN, CLOSE)}
    if OPEN not in marks or CLOSE not in marks:
        raise RuntimeError("trace window marks missing from the trace")
    w0, w1 = marks[OPEN], marks[CLOSE]
    window_s = (w1 - w0) / 1e9
    devices = sorted({d for d, *_ in ev["ops"]})
    busy, by_dev = 0.0, {}
    kernels = {k: 0.0 for k in KERNELS}
    ops_time: dict = {}
    for dev, name, start, dur, label in ev["ops"]:
        s, e = _clip(start, start + dur, w0, w1)
        if e <= s:
            continue
        by_dev.setdefault(dev, []).append((s, e))
        for k, pat in KERNELS.items():
            if pat.search(label):
                kernels[k] += (e - s) / 1e9
                name = k
                break
        else:
            name = _short(name)
        ops_time[name] = ops_time.get(name, 0.0) + (e - s) / 1e9
    merged = {d: _union(iv) for d, iv in by_dev.items()}
    for d in devices:
        busy += sum(e - s for s, e in merged.get(d, [])) / 1e9
    busy /= max(1, len(devices))
    steps = {k: 0 for k in STEPS}
    for _, name, start, dur in ev["modules"]:
        if w0 <= start < w1:
            for k, pat in STEPS.items():
                if pat.search(name):
                    steps[k] += 1
    gaps = _idle_by_host(merged.get(devices[0], []) if devices else [],
                         ev["host"], w0, w1)
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": busy, "kernels": kernels,
            "steps": steps, "n_devices": len(devices),
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": gaps}}


def _short(name: str) -> str:
    """An HLO op's instruction name and result type, without operands."""
    head, _, rest = name.partition(" = ")
    return (head + " " + rest.split(" ", 1)[0])[:100] if rest else name[:100]


def _idle_by_host(busy: list, host: list, w0: int, w1: int) -> list:
    """Idle device time inside the window, by the innermost benchmark
    span open on the host at each gap's middle (``host: other`` where
    none is): the ten largest totals."""
    spans = [(s, s + d, name) for _, name, s, d in host
             if name not in (OPEN, CLOSE)]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    out: dict = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) // 2
        inner = [sp for sp in spans if sp[0] <= mid < sp[1]]
        label = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner \
            else "host: other"
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:10]]
