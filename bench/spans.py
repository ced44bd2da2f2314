"""The serving program's own spans in the profiled part of a traced run.

The program marks what its threads do with host spans named ``engine.*``
(the tick loop) and ``gateway.*`` (the HTTP front door), written through
the JAX profiler, so they share the device trace's clock. ``events``
reads them from the ``.xplane.pb`` as tuples ``(line, name, start_ns,
dur_ns, args)``; ``line`` is the plane's name and the line's position in
it, since threads may share a line name. ``program`` takes them with
``bench/devtrace.py``'s device ops and window marks, under ``spans``
beside the lists ``devtrace.events`` gives, and returns:

- ``idle``: the device's idle time in the window, split exactly (by
  interval intersection) by the innermost program span open on the
  engine thread, ``engine: no span`` where none is; with each share, the
  part of it during which a ``gateway.*`` span was open on another line;
- ``gaps``: where the idle time in no engine span lies, by the engine
  spans that end before it and start after it;
- ``spans``: for each span name, the spans that started in the window:
  their count, total and self time (duration less the time of their
  children on the same line) and the sum of each numeric arg.

The engine thread is the line that carries ``engine.burst`` spans. Like
``devtrace.reduce``, ``program`` is pure Python over the tuples, so
``tests/bench`` checks it on a small recorded trace.
"""
from __future__ import annotations

import bisect

from bench import devtrace

PREFIXES = ("engine.", "gateway.")
NO_SPAN = "engine: no span"
BURST = "engine.burst"


def events(path) -> list:
    """The program's spans in the trace at ``path``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((f"{plane.name}/{i}", ev.name, ev.start_ns,
                                ev.duration_ns, dict(ev.stats)))
    return out


def _innermost(spans: list) -> list:
    """``(start, end, i)``: the pieces of time in which span ``i`` of
    ``spans`` (``(start, end, ...)`` on one line, nested) is the
    innermost one open, in order."""
    points = sorted({x for s, e, *_ in spans for x in (s, e)})
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    out, open_, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(order) and spans[order[k]][0] <= a:
            open_.append(order[k])
            k += 1
        open_ = [i for i in open_ if spans[i][1] > a]
        if open_:
            # nested spans: the innermost started last (and ends first)
            i = max(open_, key=lambda i: (spans[i][0], -spans[i][1]))
            out.append((a, b, i))
    return out


def _overlap(a: list, b: list):
    """Overlapping pieces ``(start, end, i, j)`` of ``a[i]`` and ``b[j]``,
    two ordered lists of disjoint ``(start, end, ...)`` intervals."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            yield s, e, i, j
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1


def program(ev: dict):
    """The idle split and self-time table (nanoseconds in, seconds out),
    or None where no line carries ``engine.burst``."""
    marks = {name: start for _, name, start, _ in ev["host"]
             if name in (devtrace.OPEN, devtrace.CLOSE)}
    w0, w1 = marks[devtrace.OPEN], marks[devtrace.CLOSE]
    by_line: dict = {}
    for line, name, start, dur, args in ev["spans"]:
        by_line.setdefault(line, []).append((start, start + dur, name, args))
    bursts = {line: sum(sp[2] == BURST for sp in sp_) for line, sp_ in
              by_line.items()}
    if not bursts or max(bursts.values()) == 0:
        return None
    engine = max(bursts, key=bursts.get)

    devices = sorted({d for d, *_ in ev["ops"]})
    busy = devtrace._union([devtrace._clip(s, s + d, w0, w1)
                            for dev, _, s, d, _ in ev["ops"]
                            if devices and dev == devices[0]
                            and min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]

    # what the engine thread was in, as a partition of the window
    eng = by_line[engine]
    parts, t = [], w0
    for a, b, i in _innermost(eng):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        if a > t:
            parts.append((t, a, NO_SPAN))
        parts.append((a, b, eng[i][2]))
        t = b
    if t < w1:
        parts.append((t, w1, NO_SPAN))
    gateway = devtrace._union([
        (s, e) for line, sp_ in by_line.items() if line != engine
        for s, e, name, _ in sp_ if name.startswith("gateway.")])
    pieces = [(s, e, parts[j][2]) for s, e, _, j in _overlap(idle, parts)]
    split: dict = {}
    for s, e, label in pieces:
        split.setdefault(label, [0, 0])[0] += e - s
    for s, e, i, _ in _overlap(pieces, gateway):
        split[pieces[i][2]][1] += e - s
    ends = sorted((e, name) for _, e, name, _ in eng)
    starts = sorted((s, name) for s, _, name, _ in eng)
    end_t, start_t = [e for e, _ in ends], [s for s, _ in starts]
    gaps: dict = {}
    for s, e, label in pieces:
        if label != NO_SPAN:
            continue
        k = bisect.bisect_right(end_t, s)
        j = bisect.bisect_left(start_t, e)
        key = (f"{ends[k - 1][1] if k else 'open'} -> "
               f"{starts[j][1] if j < len(starts) else 'close'}")
        row = gaps.setdefault(key, [0, 0])
        row[0] += e - s
        row[1] += 1

    table: dict = {}
    for sp_ in by_line.values():
        self_ns = [0] * len(sp_)
        for a, b, i in _innermost(sp_):
            self_ns[i] += b - a
        for (s, e, name, args), own in zip(sp_, self_ns):
            if not w0 <= s < w1:
                continue
            row = table.setdefault(name, {"n": 0, "total_s": 0.0,
                                          "self_s": 0.0, "args": {}})
            row["n"] += 1
            row["total_s"] += (e - s) / 1e9
            row["self_s"] += own / 1e9
            for k, v in args.items():
                if isinstance(v, (int, float)):
                    row["args"][k] = row["args"].get(k, 0) + v
    return {"engine_line": engine,
            "idle_s": sum(e - s for s, e in idle) / 1e9,
            "idle": [[label, ns / 1e9, gw / 1e9] for label, (ns, gw) in
                     sorted(split.items(), key=lambda kv: -kv[1][0])],
            "gaps": [[key, ns / 1e9, n] for key, (ns, n) in
                     sorted(gaps.items(), key=lambda kv: -kv[1][0])],
            "spans": table}


def note(ctx: dict, prog: dict) -> None:
    """Add the idle split (ten largest shares) and the self-time table to
    the run's notes, once."""
    idle = prog["idle_s"]
    none = sum(s for label, s, _ in prog["idle"] if label == NO_SPAN)
    text = (
        f"program spans: device idle {idle:.6f} s, "
        f"{100 * (1 - none / idle) if idle else 0.0:.1f}% of it under a "
        "span of the engine thread; by innermost span (s, of which a "
        "gateway span was open): " + "; ".join(
            f"{label} {s:.6f} ({gw:.6f})" for label, s, gw in
            prog["idle"][:10])
        + " | idle in no span, between (s, gaps): " + "; ".join(
            f"{key} {s:.6f} {n}" for key, s, n in prog["gaps"][:5])
        + " | self time (n, total s, self s): " + "; ".join(
            f"{name} {row['n']} {row['total_s']:.6f} {row['self_s']:.6f}"
            for name, row in sorted(prog["spans"].items(),
                                    key=lambda kv: -kv[1]["self_s"])))
    notes = ctx.setdefault("notes", [])
    if text not in notes:
        notes.append(text)
