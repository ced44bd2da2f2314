"""The program-span reduction (``bench/spans.py``) and its three readers on
a small recorded trace, worked by hand; the reader of the program's
spans on a real profiler trace; and a CPU rehearsal of
``bench/program_trace.py``."""
import json
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spans  # noqa: E402
from bench.layer_metrics import read  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def prog():
    return spans.program(json.loads((DATA / "trace_spans.json").read_text()))


def test_engine_line_is_the_one_with_bursts(prog):
    assert prog["engine_line"] == "/host:CPU/0"


def test_idle_is_split_exactly_by_the_innermost_engine_span(prog):
    # idle [3000,5000) [6000,9000) [10000,11000): 6000 ns
    assert prog["idle_s"] == pytest.approx(6_000e-9)
    got = {label: s for label, s, _ in prog["idle"]}
    assert got == pytest.approx({
        "engine.burst.dispatch": 1_000e-9,      # [3000,4000)
        "engine.burst.sync": 1_000e-9,          # [4000,5000)
        "engine.burst.emit": 1_000e-9,          # [6000,7000)
        "engine.burst": 1_100e-9,               # [7000,8000) [10900,11000)
        "engine.pump": 200e-9,
        "engine.admit": 400e-9,
        "engine.wait": 600e-9,
        spans.NO_SPAN: 700e-9,                  # [8600,9000) [10000,10200)
    })                                          # [10800,10900)
    assert [label for label, *_ in prog["idle"]][0] == "engine.burst"


def test_idle_with_a_gateway_span_open(prog):
    gw = {label: g for label, _, g in prog["idle"]}
    # write [6500,7500), parse [8100,8300); the write at 9500 is busy time
    assert gw == pytest.approx({
        "engine.burst.emit": 500e-9, "engine.burst": 500e-9,
        "engine.pump": 100e-9, "engine.admit": 100e-9,
        "engine.burst.dispatch": 0.0, "engine.burst.sync": 0.0,
        "engine.wait": 0.0, spans.NO_SPAN: 0.0})


def test_idle_in_no_span_by_its_neighbours(prog):
    # [8600,9000) and [10000,10200) after admit, before wait; [10800,
    # 10900) after wait, before the second burst
    assert [key for key, *_ in prog["gaps"]] == [
        "engine.admit -> engine.wait", "engine.wait -> engine.burst"]
    assert [n for *_, n in prog["gaps"]] == [2, 1]
    assert [s for _, s, _ in prog["gaps"]] == pytest.approx([600e-9,
                                                            100e-9])


def test_self_time_of_spans_started_in_the_window(prog):
    t = prog["spans"]
    burst = t["engine.burst"]
    # two bursts start inside; the first's children cover 5000 of its
    # 6000 ns, the second's emit (after the window) 400 of its 1100
    assert burst["n"] == 2
    assert burst["total_s"] == pytest.approx(7_100e-9)
    assert burst["self_s"] == pytest.approx(1_700e-9)
    assert burst["args"] == {"burst": 3, "slots": 8, "ticks": 3, "ctx": 88}
    emit = t["engine.burst.emit"]
    assert (emit["n"], emit["args"]) == (1, {"tokens": 8})
    assert emit["self_s"] == pytest.approx(1_500e-9)
    assert t["gateway.write"]["n"] == 2
    assert t["gateway.write"]["self_s"] == pytest.approx(1_300e-9)
    assert t["gateway.write"]["args"] == {"uid": 0}


def test_no_engine_bursts_reads_nothing():
    ev = json.loads((DATA / "trace_spans.json").read_text())
    ev["spans"] = [s for s in ev["spans"] if s[1] != spans.BURST]
    assert spans.program(ev) is None


def test_readers(prog):
    ctx = {"trace": {"program": prog,
                     "counters": ({"kv.block_ticks_used": 100,
                                   "kv.block_ticks_reserved": 400},
                                  {"kv.block_ticks_used": 700,
                                   "kv.block_ticks_reserved": 1400})}}
    # engine spans but engine.wait over the 2 bursts: 4700 ns / 2
    assert read("host_idle_ms_per_burst", ctx) == pytest.approx(2_350e-6)
    assert read("emit_us_per_token", ctx) == pytest.approx(1_500e-3 / 8)
    assert read("kv_blocks_used_share", ctx) == pytest.approx(60.0)
    assert len(ctx["notes"]) == 1               # the split, noted once
    assert "88.3% of it under a span of the engine thread" in \
        ctx["notes"][0]


def test_readers_find_nothing_without_the_program():
    ctx = {"trace": {"window_s": 1.0, "busy_s": 0.5}}
    for name in ("host_idle_ms_per_burst", "emit_us_per_token",
                 "kv_blocks_used_share"):
        assert read(name, ctx) is None


def test_events_reads_program_spans_from_a_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    def engine():
        with TraceAnnotation("engine.burst", slots=2) as sp:
            with TraceAnnotation("bench.decode_step"):
                time.sleep(0.001)
            sp.set_metadata(ticks=3)

    jax.profiler.start_trace(str(tmp_path))
    t = threading.Thread(target=engine)
    t.start()
    t.join(timeout=60)
    with TraceAnnotation("gateway.write", uid=4, event="token"):
        pass
    jax.profiler.stop_trace()
    ev = spans.events(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    by = {name: (line, args) for line, name, _, _, args in ev}
    assert set(by) == {"engine.burst", "gateway.write"}
    assert by["engine.burst"][1] == {"slots": 2, "ticks": 3}
    assert by["gateway.write"][1] == {"uid": 4, "event": "token"}
    assert by["engine.burst"][0] != by["gateway.write"][0]


def test_program_trace_rehearsal(monkeypatch):
    import tiny
    from bench import peaks, program_trace
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    lines = []
    spec = tiny.spec("phi3-medium-14b", "decode-long")
    # a span is in the trace only if it opened and closed inside it, and
    # a decode burst takes a good part of a second on the CPU
    spec["cell"] = dict(spec["cell"], trace_s=4.0)
    out = program_trace.run_traced(
        spec, 7, 6.0, time.monotonic(), cache=False,
        log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    assert out["correct"], out["compared"]
    names = set(out["metrics"])
    assert {m["name"] for m in program_trace.METRICS} <= names
    assert 0 < out["metrics"]["kv_blocks_used_share"]["value"] <= 100
    assert any(line.startswith("program spans: device idle")
               for line in lines)
    assert any(line.startswith("end to end: ") for line in lines)
    # the patches are gone again
    from bench import devtrace, harness
    assert devtrace.Profile.__module__ == "bench.devtrace"
    assert harness.end_to_end.__module__ == "bench.harness"
