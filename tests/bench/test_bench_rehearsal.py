"""CPU rehearsal of ``bench/run.py``: every phase after the look for the
chip (configuration, prune, selection, engine behind the gateway, the
child-process load generator, one short window, the reference) for the
two configurations under the two mixes (a cell is a configuration file
and a mix file put together, so all four pairs run), at small widths
with the Pallas kernels in interpret mode; the shape of the last line;
and the command itself refusing to run without a TPU."""
import json
import math
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness, peaks

ROOT = tiny.ROOT
PAIRS = [("phi3-medium-14b", "decode-long"), ("qwen2-vl-2b", "chat"),
         ("phi3-medium-14b", "chat"), ("qwen2-vl-2b", "decode-long")]


def _run(config, mix, seed, trace=False):
    lines = []
    out = harness.run_cell(tiny.spec(config, mix), seed, 6.0, trace,
                           time.monotonic(), cache=False,
                           log=lambda *a, **k: lines.append(" ".join(
                               map(str, a))))
    return out, lines


@pytest.mark.parametrize("config,mix", PAIRS)
def test_rehearsal_runs_every_phase(config, mix):
    out, lines = _run(config, mix, 2 ** 31 + 5)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0
    # a closed loop sends only as its requests end, so a short window on
    # a busy CPU may see none sent; an open loop's requests fall due
    assert out["attempted"] > 0 or mix == "decode-long"
    spec = tiny.spec(config, mix)
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        v = out["metrics"][m["name"]]
        assert v["unit"] == m["unit"]
        if m["name"] != "hbm_in_use_gb":        # the CPU reports no HBM
            assert 0 < v["value"] < math.inf
    assert any(line.startswith("compilations in the window: 0")
               for line in lines), lines
    assert any(line.startswith("load generator late") for line in lines)
    assert any(line.startswith("device: cpu x") for line in lines)


def test_last_line_shape():
    out, _ = _run("phi3-medium-14b", "decode-long", 11)
    assert list(out)[-1] == "compared"
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, c in out["compared"].items():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(out))


def test_traced_rehearsal_reads_per_layer_metrics(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    out, _ = _run("phi3-medium-14b", "decode-long", 3, trace=True)
    assert out["correct"], out["compared"]
    names = set(out["metrics"])
    # the CPU backend's trace has no device plane: what the device trace
    # gives is left out, what the host and program record is read
    assert {"prune_pipeline_s", "slot_occupancy", "mfu"} <= names
    assert "block_sparse_roofline" not in names
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_command_exits_nonzero_without_a_tpu(tmp_path):
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "phi3-medium-14b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert not r.stdout.strip()


def test_command_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi3-medium-14b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0
    assert not r.stdout.strip()
