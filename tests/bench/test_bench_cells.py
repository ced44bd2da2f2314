"""Every cell of ``BENCHMARK.json`` loads as ``bench/run.py`` loads it:
its configuration file, model module, traffic mix and cell file resolve
through ``harness.load_spec``, the cell file holds every key the harness
reads of it, its traffic draws at the cell's own sizes and fits the
served context, and every per-layer metric that names the cell moves an
end-to-end metric the cell reports."""
import json

import pytest

import tiny
from bench import harness, traffic

ROOT = tiny.ROOT
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# what the harness reads of a cell file; an open loop also needs its rate
CELL_KEYS = {"preroll_s", "drain_s", "trace_offset_s", "trace_s",
             "check_requests", "logit_gap_mean_limit"}


def _reports(cell: str, metric: dict) -> bool:
    return cell in metric.get("workloads", [cell])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_through_load_spec(cell):
    spec = harness.load_spec(cell, BENCHMARK)
    w = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCHMARK["configs"] if c["name"] == w["config"])
    assert ROOT / conf["file"] == \
        ROOT / "bench" / "configs" / f"{w['config']}.json"
    assert spec["conf"]["name"] == w["config"]
    assert spec["conf"]["reduced"] == conf["reduced"]
    assert spec["chips"] == w["chips"]

    keys = CELL_KEYS | ({"rate_rps"} if spec["mix"]["loop"] == "open"
                        else set())
    assert keys <= set(spec["cell"]), keys - set(spec["cell"])

    serve = spec["conf"]["serve"]
    assert traffic.max_context(spec["mix"]) <= serve["max_seq"]
    plan = traffic.draw(spec["mix"], spec["cell"], spec["conf"]["vocab_size"],
                        serve["max_slots"], 2 ** 31 + 9,
                        BENCHMARK["run_seconds"])
    assert plan["requests"]
    assert spec["end_to_end"] and spec["per_layer"]
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=[m["name"] for m in BENCHMARK["per_layer"]])
def test_per_layer_cells_exist_and_report_what_it_moves(metric):
    moves = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == metric["moves"])
    named = metric.get("workloads", [c for c in CELLS
                                     if _reports(c, moves)])
    assert named
    for cell in named:
        assert cell in CELLS
        assert _reports(cell, moves), (metric["name"], cell)
        spec = harness.load_spec(cell, BENCHMARK)
        assert metric["name"] in {m["name"] for m in spec["per_layer"]}
