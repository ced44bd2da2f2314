"""Find a chat cell's knee: one set-up, then the cell's open-loop mix at
each of a few fixed rates in turn, through the same gateway.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1 2 4 8

One line per rate: requests due in the window and failed, TTFT and TPOT
at the 90th percentile, tokens/s, and whether the backlog grew (median
queue wait of the first and last third of the window's requests, and
requests in flight at the window's start and end). The knee is the
highest rate at which the backlog does not grow and nothing fails; a
cell's ``rate_rps`` is set near four fifths of it. The benchmark's own
runs never sweep.
"""
import argparse
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def backlog(records: list, window: tuple) -> dict:
    """Queue-wait medians of the first and last third of the requests
    due in the window, and requests in flight at its two ends."""
    due = sorted((r for r in records if window[0] <= r["due"] < window[1]),
                 key=lambda r: r["due"])
    waits = [r["end"]["metrics"]["queue_ms"] if r["status"] == "done"
             else float("inf") for r in due]
    third = max(1, len(waits) // 3)

    def flying(t):
        return sum(r["sent"] is not None and r["sent"] <= t
                   and (not r["bursts"] or r["bursts"][-1][0] > t)
                   for r in records)
    return {"queue_p50_first_ms": statistics.median(waits[:third])
            if waits else None,
            "queue_p50_last_ms": statistics.median(waits[-third:])
            if waits else None,
            "in_flight_start": flying(window[0]),
            "in_flight_end": flying(window[1])}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, traffic
    from bench.run import require_chips
    spec = harness.load_spec(args.workload, json.loads(
        (ROOT / "BENCHMARK.json").read_text()))
    require_chips(spec["chips"])
    st = harness.prepare(spec, args.seed)
    tmp = Path(tempfile.mkdtemp(prefix="bench-sweep-"))
    try:
        for rate in args.rates:
            cell = dict(spec["cell"], rate_rps=rate)
            plan = traffic.draw(spec["mix"], cell, st.vocab,
                                st.serve.max_slots, args.seed, args.seconds)
            client, window, _ = harness.drive(st.port, plan, cell,
                                              args.seconds, st.dev, tmp,
                                              closing=st.rec.closing)
            e2e = harness.end_to_end(client["records"], window, "open")
            line = {"rate_rps": rate, **e2e,
                    **backlog(client["records"], window),
                    "late_p99_ms": client["late_s"]["p99"] * 1e3}
            print(json.dumps(line), flush=True)
    finally:
        st.gw.close(timeout=600)
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
