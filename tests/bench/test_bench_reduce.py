"""The reduction from trace to metrics (``bench/devtrace.py``) on a
small hand-built trace, the operation and byte counts of ``bench/work``
against hand-computed values, the peaks table, and the end-to-end
arithmetic of ``bench/harness.py``."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, peaks  # noqa: E402
from bench.layer_metrics import decode_work, prefill_work  # noqa: E402
from bench.models import dense  # noqa: E402
from bench.work import block_sparse, model_step, paged_attention  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce(json.loads((DATA / "trace_small.json")
                                      .read_text()))


def test_window_is_read_between_the_host_marks(reduced):
    assert reduced["window_s"] == pytest.approx(10_000e-9)
    assert reduced["n_devices"] == 1


def test_busy_is_the_union_of_op_intervals_inside_the_window(reduced):
    # [1000, 4000) (two overlapping ops), [6000, 7000), [10500, 11000)
    assert reduced["busy_s"] == pytest.approx(4_500e-9)


def test_kernel_time_per_kernel(reduced):
    assert reduced["kernels"]["block_sparse"] == pytest.approx(2_000e-9)
    assert reduced["kernels"]["paged_attention"] == pytest.approx(1_000e-9)


def test_steps_counted_inside_the_window(reduced):
    assert reduced["steps"] == {"decode": 1, "prefill": 1}


def test_breakdown_lists_ops_and_idle_by_host_span(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["block_sparse"] == pytest.approx(2_000e-9)
    assert ops["%fusion.7 bf16[64,512]{1,0}"] == pytest.approx(1_500e-9)
    assert ops["%fusion.9 f32[4096]{0}"] == pytest.approx(500e-9)
    assert ops["paged_attention"] == pytest.approx(1_000e-9)
    idle = dict(reduced["breakdown"]["idle_gaps"])
    # gap [4000, 6000): inside decode_burst; gap [7000, 10500): its
    # middle 8750 lies in sample_first, the innermost span
    assert idle == pytest.approx({"bench.decode_burst": 2_000e-9,
                                  "bench.sample_first": 3_500e-9})
    assert len(reduced["breakdown"]["device_ops"]) <= 10


def test_missing_marks_are_an_error():
    ev = json.loads((DATA / "trace_small.json").read_text())
    ev["host"] = [h for h in ev["host"] if h[1] != devtrace.CLOSE]
    with pytest.raises(RuntimeError):
        devtrace.reduce(ev)


def test_block_sparse_call_counts():
    ops, by = block_sparse.call(rows=4, K=256, N=512, kept=3, block=128)
    assert ops == 2 * 4 * 3 * 128 * 128
    assert by == 3 * 128 * 128 * 2 + 4 * (256 + 512) * 2


def test_paged_attention_counts():
    ops, by = paged_attention.token(n_q=8, n_kv=2, head_dim=32, ctx=10)
    assert ops == 4 * 8 * 32 * 10
    assert by == 2 * 2 * 32 * 10 * 2 + 2 * 8 * 32 * 2
    st = {"layers": [{"n_q": 8, "n_kv": 2, "head_dim": 32},
                     {"n_q": 4, "n_kv": 2, "head_dim": 32}]}
    # linear in the context: three tokens at 5, 10 and 15 positions
    want = [sum(x) for x in zip(*[
        paged_attention.token(lay["n_q"], lay["n_kv"], 32, c)
        for lay in st["layers"] for c in (5, 10, 15)])]
    assert list(paged_attention.decode(st, 3, 30)) == want


def test_model_step_counts():
    st = {"d": 256, "vocab": 512, "block": 128, "layers": [
        {"n_q": 8, "head_dim": 32,
         "proj": {"q": (256, 256, 2), "o": (256, 256, None)}}]}
    per = 2 * (2 * 128 * 128) + 2 * 256 * 256 + 2 * 256 * 512
    assert dense.ops_per_token(st) == per
    assert dense.ops_per_context(st) == 4 * 8 * 32
    st = dict(st, ops_per_token=per, ops_per_context=4 * 8 * 32)
    assert model_step.tokens(st, 3, 30) == 3 * per + 30 * 4 * 8 * 32


def test_decode_and_prefill_work_from_the_record():
    # two slots whose lengths sum to 10, three ticks: contexts 12, 14, 16
    assert decode_work([(0, 1, 2, 3, 10)]) == (6, 42)
    # a chunk of 4 tokens from position 6: contexts 7, 8, 9, 10
    assert prefill_work([(0, 1, 6, 4)]) == (4, 34)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_end_to_end_arithmetic():
    from bench.harness import end_to_end, percentile
    w = (100.0, 110.0)
    recs = [
        # due in the window, first token 0.2 s late, four tokens inside
        {"i": 0, "due": 101.0, "sent": 101.0, "status": "done",
         "bursts": [[101.2, 1], [101.5, 2], [101.8, 1]]},
        # due in the window, failed: counts as infinitely late
        {"i": 1, "due": 105.0, "sent": 105.0, "status": "rejected",
         "bursts": []},
        # due before the window: tokens inside count, its TTFT does not
        {"i": 2, "due": 99.0, "sent": 99.0, "status": "done",
         "bursts": [[99.5, 1], [109.0, 3], [111.0, 2]]},
    ]
    e = end_to_end(recs, w, "open")
    assert e["attempted"] == 2 and e["failed"] == 1
    assert e["ttft_p90_ms"] == math.inf
    assert e["tokens_per_s"] == pytest.approx((4 + 3) / 10)
    assert e["n_tpot"] == 2
    tpots = sorted([(101.8 - 101.2) / 3, 0.0])
    assert e["tpot_p90_ms"] == pytest.approx(tpots[-1] * 1e3)
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert percentile([3.0], 90) == 3.0


def test_request_readers_count_the_window():
    from types import SimpleNamespace
    from bench.layer_metrics import read
    w = (10.0, 20.0)

    def rec(last, queue_ms, prompt_len, shared, prefix="t0", status="done"):
        return {"status": status, "bursts": [[last - 1, 1], [last, 1]],
                "prompt_len": prompt_len, "prefix_id": prefix,
                "end": {"metrics": {"queue_ms": queue_ms},
                        "prompt_blocks_shared": shared}}
    records = [rec(12, 5.0, 33, 1), rec(15, 7.0, 49, 2),
               rec(25, 900.0, 49, 0),            # finished after the window
               rec(18, 1.0, 20, 0, prefix=None),  # no prefix_id
               rec(19, 3.0, 33, 0, status="rejected")]
    ctx = {"records": records, "window": w,
           "serve": SimpleNamespace(block_size=16)}
    # done in the window: queue waits 5, 7, 1 -> nearest-rank p90 is 7
    assert read("queue_wait_p90_ms", ctx) == 7.0
    # shareable blocks (33-1)//16 + (49-1)//16 = 2 + 3, shared 1 + 2
    assert read("prefix_hit_rate", ctx) == pytest.approx(100 * 3 / 5)
    assert read("prefix_hit_rate", dict(ctx, records=records[3:4])) is None


def test_resident_bytes_waits_for_two_reads_that_agree(monkeypatch):
    from bench import harness
    monkeypatch.setattr(harness.time, "sleep", lambda s: None)
    reads = iter([9, 7, 5, 5, 4])

    class Dev:
        def memory_stats(self):
            return {"bytes_in_use": next(reads), "peak_bytes_in_use": 9}
    assert harness.resident_bytes(Dev()) == 5

    class Cpu:
        def memory_stats(self):
            return None
    assert harness.resident_bytes(Cpu()) == 0
