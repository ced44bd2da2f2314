"""The traffic generator (``bench/traffic.py``) keeps its parameters:
deterministic for a seed, different across seeds, the same stratified
sizes for every seed, the tenant share and Zipf skew, and an arrival
count that matches the rate."""
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

MIXES = {name: json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                          .read_text()) for name in ("decode-long", "chat")}
CELLS = {"decode-long": {"preroll_s": 5.0},
         "chat": {"preroll_s": 5.0, "rate_rps": 8.0}}
VOCAB, SLOTS, SECONDS = 32064, 64, 30.0
SEEDS = (0, 7, 2 ** 31 + 11, 2 ** 40 + 3)


def draw(mix, seed):
    return traffic.draw(MIXES[mix], CELLS[mix], VOCAB, SLOTS, seed, SECONDS)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_requests(mix):
    assert draw(mix, 5) == draw(mix, 5)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_seeds_differ_in_order_and_tokens(mix):
    a, b = draw(mix, 1)["requests"], draw(mix, 2)["requests"]
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert [r["max_new_tokens"] for r in a] != [r["max_new_tokens"]
                                                 for r in b]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_stratum_holds_the_same_sizes(mix, seed):
    m = MIXES[mix]
    reqs = draw(mix, seed)["requests"]
    st = m["stratum"]
    want_out = sorted(traffic.quantile_set(st, m["output"]).tolist())
    want_tail = sorted(traffic.quantile_set(st, m["prompt"]).tolist())
    for k in range(len(reqs) // st):
        block = reqs[k * st:(k + 1) * st]
        assert sorted(r["max_new_tokens"] for r in block) == want_out
        assert sorted(len(r["tokens"]) - r["system_len"]
                      for r in block) == want_tail
    if m["prefixes"]:
        lens = {r["prefix_id"]: r["system_len"] for r in reqs
                if r["prefix_id"]}
        assert set(lens.values()) <= set(_system_lengths(m))


def _system_lengths(m):
    p = m["prefixes"]
    return sorted(np.rint(np.linspace(p["min"], p["max"], p["count"]))
                  .astype(int).tolist(), reverse=True)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_keep_median_and_clips(mix):
    m = MIXES[mix]
    for key in ("prompt", "output"):
        d = m[key]
        s = traffic.quantile_set(m["stratum"], d)
        assert s.min() >= d["min"] and s.max() <= d["max"]
        assert abs(np.median(s) - d["median"]) <= 0.05 * d["median"]
        big = traffic.quantile_set(4096, d)
        assert abs(np.median(big) - d["median"]) <= 1
        assert big.min() == d["min"] or d["sigma"] < 0.5
        # the log-spread is the mix's sigma where no clip binds
        inner = np.log(big[(big > d["min"]) & (big < d["max"])])
        assert np.std(inner) < d["sigma"] * 1.05


def test_chat_tenant_share_and_zipf_skew():
    m, reqs = MIXES["chat"], draw("chat", 3)["requests"]
    st, pre = m["stratum"], m["prefixes"]
    full = len(reqs) // st * st
    block_reqs = reqs[:full]
    share = np.mean([r["prefix_id"] is not None for r in block_reqs])
    assert share == pytest.approx(round(pre["share"] * st) / st)
    counts = {}
    for r in block_reqs:
        if r["prefix_id"]:
            counts[r["prefix_id"]] = counts.get(r["prefix_id"], 0) + 1
    by_rank = sorted(counts.values(), reverse=True)
    want = traffic.zipf_counts(round(pre["share"] * st), pre["count"],
                               pre["zipf_s"]) * (full // st)
    assert by_rank == sorted(want[want > 0].tolist(), reverse=True)
    # Zipf s = 1.1 over 16 tenants: the top tenant takes 1/H(16, 1.1)
    h = sum(1 / k ** pre["zipf_s"] for k in range(1, pre["count"] + 1))
    assert by_rank[0] / sum(by_rank) == pytest.approx(1 / h, abs=0.02)
    # every tenant's requests start with that tenant's system prompt
    firsts = {}
    for r in block_reqs:
        if r["prefix_id"]:
            firsts.setdefault(r["prefix_id"], r["tokens"][:pre["min"]])
            assert r["tokens"][:pre["min"]] == firsts[r["prefix_id"]]


@pytest.mark.parametrize("seed", SEEDS)
def test_chat_arrivals_match_the_rate(seed):
    cell = CELLS["chat"]
    reqs = draw("chat", seed)["requests"]
    t = np.array([r["send_at"] for r in reqs])
    assert (np.diff(t) > 0).all()
    w0 = cell["preroll_s"]
    in_window = ((t >= w0) & (t < w0 + SECONDS)).sum()
    lam = cell["rate_rps"] * SECONDS
    assert abs(in_window - lam) <= 3 * math.sqrt(lam)


def test_closed_loop_pool_and_concurrency():
    plan = draw("decode-long", 9)
    m = MIXES["decode-long"]
    assert plan["loop"] == "closed"
    assert plan["concurrency"] == math.ceil(m["concurrency_per_slot"] * SLOTS)
    assert len(plan["requests"]) == m["stratum"] * m["closed_pool_strata"]
    assert all(r["prefix_id"] is None and r["send_at"] is None
               for r in plan["requests"])
    assert all(0 <= t < VOCAB for r in plan["requests"][:50]
               for t in r["tokens"])


def _first(seed):
    return draw("decode-long", seed)["first"]


def test_first_wave_holds_the_same_sizes_for_every_seed():
    m = MIXES["decode-long"]
    sizes = {s: sorted((len(r["tokens"]), r["max_new_tokens"], r["age"])
                       for r in _first(s)) for s in SEEDS}
    assert all(v == sizes[SEEDS[0]] for v in sizes.values())
    assert len(sizes[SEEDS[0]]) == SLOTS
    a, b = _first(1), _first(2)
    assert [r["max_new_tokens"] for r in a] != [r["max_new_tokens"]
                                                 for r in b]
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert draw("chat", 1)["first"] == []
    for r in a:
        assert r["max_new_tokens"] >= 1 and r["age"] >= 0
        assert len(r["tokens"]) + r["max_new_tokens"] \
            <= traffic.max_context(m)


def test_first_wave_is_the_steady_state_of_the_slots():
    m = MIXES["decode-long"]
    first = _first(3)
    total = np.array([r["age"] + r["max_new_tokens"] for r in first])
    prompt = np.array([len(r["tokens"]) - r["age"] for r in first])
    # a slot holds a request in proportion to its length: E[L^2] / E[L]
    big = traffic.quantile_set(4096, m["output"]).astype(float)
    assert total.mean() == pytest.approx((big ** 2).sum() / big.sum(),
                                         rel=0.05)
    assert total.min() >= m["output"]["min"]
    assert total.max() <= m["output"]["max"]
    # ages spread evenly over each length: the slots' contexts differ
    frac = np.sort(np.array([r["age"] for r in first]) / total)
    assert np.abs(frac - (np.arange(SLOTS) + 0.5) / SLOTS).max() < 0.05
    assert sorted(prompt.tolist()) == sorted(
        traffic.quantile_set(SLOTS, m["prompt"]).tolist())


def test_sends_take_the_first_wave_then_the_pool():
    plan = draw("decode-long", 4)
    n, pool = len(plan["first"]), plan["requests"]
    assert traffic.request(plan, 0) is plan["first"][0]
    assert traffic.request(plan, n - 1) is plan["first"][-1]
    assert traffic.request(plan, n) is pool[0]
    assert traffic.request(plan, n + len(pool)) is pool[0]
