"""Model modules are found by the name a configuration file gives
(``bench/models``): every configuration names one whose program config
takes the file at its published widths, and a configuration of a new
architecture is added as files alone. The latter is shown on a copy of
the benchmark's files with a new module, configurations and cells added
and nothing else changed: ``harness.load_spec`` finds the module, a run
of the whole harness calls it at set-up and in the comparison, and a
configuration that names no module, or a missing or incomplete one,
fails with a message naming the file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
from bench import models

ROOT = tiny.ROOT
HERE = Path(__file__).resolve().parent
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))

# A dense variant that records each call of its five functions and reads
# every served token one logit below the reference's best.
GAPPED = '''
from bench.models import dense

CALLS = []


def _recorded(name):
    def call(*a, **k):
        CALLS.append(name)
        return getattr(dense, name)(*a, **k)
    return call


program_config = _recorded("program_config")
init_params = _recorded("init_params")
extract_selection = _recorded("extract_selection")
structure = _recorded("structure")


def served_gaps(*a, **k):
    CALLS.append("served_gaps")
    return [g + 1.0 for g in dense.served_gaps(*a, **k)]
'''
PARTIAL = "from bench.models.dense import (extract_selection, init_params,\n" \
    "                                 program_config, structure)\n"
ADDED = {"phi3-gapped": "gapped", "phi3-nomodel": None,
         "phi3-absent": "absent", "phi3-partial": "partial"}

LOAD = r"""
import json, sys
from pathlib import Path
sys.path[:0] = sys.argv[1:3]
from bench import harness
bench = json.loads(Path("BENCHMARK.json").read_text())
out = {}
for cell in sys.argv[3:]:
    try:
        out[cell] = {"module": harness.load_spec(cell, bench)["model"].__file__}
    except Exception as e:
        out[cell] = {"error": f"{type(e).__name__}: {e}"}
print(json.dumps(out))
"""

RUN = r"""
import json, sys, time
sys.path[:0] = sys.argv[1:4]
sys.path.append(sys.argv[4])
import tiny
from bench import harness
tiny.WIDTHS["phi3-gapped"] = tiny.WIDTHS["phi3-medium-14b"]
spec = tiny.spec("phi3-gapped", "chat", "float32")
out = harness.run_cell(spec, 5, 2.0, False, time.monotonic(), cache=False,
                       log=lambda *a, **k: None)
print(json.dumps({"module": spec["model"].__file__,
                  "calls": spec["model"].CALLS, "correct": out["correct"],
                  "compared": out["compared"]}))
"""


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_every_configuration_names_a_module_that_takes_it(path):
    conf = json.loads(path.read_text())
    assert conf["name"] == path.stem
    mod = models.load(conf)
    assert Path(mod.__file__) == ROOT / "bench" / "models" / \
        f"{conf['model']}.py"
    cfg = mod.program_config(conf)
    assert cfg.d_model == conf["hidden_size"]
    assert cfg.vocab == conf["vocab_size"]


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    """The benchmark's files with a module, configurations and cells
    added (``ADDED``: configuration name to the module it names)."""
    top = tmp_path_factory.mktemp("overlay")
    bench = top / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "models" / "gapped.py").write_text(GAPPED)
    (bench / "models" / "partial.py").write_text(PARTIAL)
    base = json.loads((bench / "configs" / "phi3-medium-14b.json")
                      .read_text())
    cell = (bench / "cells" / "phi3-medium-14b.decode-long.json").read_text()
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, module in ADDED.items():
        conf = dict(base, name=name)
        conf.pop("model")
        if module is not None:
            conf["model"] = module
        (bench / "configs" / f"{name}.json").write_text(json.dumps(conf))
        (bench / "cells" / f"{name}.decode-long.json").write_text(cell)
        benchmark["configs"].append(dict(benchmark["configs"][0], name=name,
                                         file=f"bench/configs/{name}.json"))
        benchmark["workloads"].append(dict(
            benchmark["workloads"][0], name=f"{name}.decode-long",
            config=name))
    (top / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return top


def _child(script, args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script, *map(str, args)],
                       cwd=cwd, capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def loaded(overlay):
    return _child(LOAD, [overlay, ROOT / "src"] +
                  [f"{name}.decode-long" for name in ADDED], overlay)


def test_load_spec_finds_an_added_module(overlay, loaded):
    assert loaded["phi3-gapped.decode-long"] == {
        "module": str(overlay / "bench" / "models" / "gapped.py")}


@pytest.mark.parametrize("name,names", [
    ("phi3-nomodel", ["bench/configs/phi3-nomodel.json", '"model"']),
    ("phi3-absent", ["bench/configs/phi3-absent.json",
                     "bench/models/absent.py"]),
    ("phi3-partial", ["bench/models/partial.py", "served_gaps"]),
])
def test_a_configuration_without_its_module_fails_naming_the_file(
        loaded, name, names):
    error = loaded[f"{name}.decode-long"]["error"]
    for part in names:
        assert part in error


def test_the_harness_runs_an_added_module(overlay):
    out = _child(RUN, [overlay, ROOT / "src", HERE, ROOT], overlay)
    assert out["module"] == str(overlay / "bench" / "models" / "gapped.py")
    assert out["calls"] == ["program_config", "init_params",
                            "extract_selection", "structure",
                            "init_params", "served_gaps"]
    # served in float32, a sound run's gaps are 0: the module's own
    # reference, one logit off everywhere, is what the run compared
    assert out["correct"] is False
    assert out["compared"]["artifact_mismatch"]["value"] == 0
    assert out["compared"]["stream_mismatch"]["value"] == 0
    assert out["compared"]["logit_gap_mean"]["value"] == pytest.approx(1.0)
