"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and the cell's load level
(``bench/cells/<cell>.json``) and draws the requests from ``--seed``.

Sizes are stratified: every block of ``stratum`` requests holds the same
set of prompt and output lengths (quantiles of the mix's distribution at
``(i + 0.5) / stratum``), the same number of requests per tenant, and an
open loop the same set of gaps between arrivals (quantiles of the
exponential); the seed draws their order and the token ids. So two seeds
ask for the same work in another order, and a run's spread comes from
the system, not from the draw. Imports no JAX: the load generator's
process uses it.

A closed loop whose mix sets ``"first_wave": "equilibrium"`` starts as a
loop that has run for a while: its first ``max_slots`` sends
(``plan["first"]``) are the requests a slot holds in the steady state,
each with a length drawn in proportion to its length (a long request
occupies a slot longer), an age spread evenly over that length, the
tokens of that age appended to its prompt (prefilled, not decoded) and
the rest of the length left to decode. So the slots hold a spread of
context lengths and end at spread times from the start, and no
synchronised first wave reaches the window.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_set(n: int, dist: dict) -> np.ndarray:
    """``n`` lengths at the mid quantiles of ``dist`` (a lognormal with
    ``median`` and ``sigma``), rounded and clipped to ``[min, max]``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def zipf_counts(total: int, n: int, s: float) -> np.ndarray:
    """``total`` split over ``n`` ranks in proportion to ``1 / k**s``
    (largest remainder, so the counts sum exactly)."""
    w = 1.0 / np.arange(1, n + 1) ** s
    exact = total * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:total - counts.sum()]] += 1
    return counts


def _stratified(rng, n: int, stratum: int, dist: dict) -> np.ndarray:
    base = quantile_set(stratum, dist)
    blocks = [rng.permutation(base) for _ in range(-(-n // stratum))]
    return np.concatenate(blocks)[:n]


def _tenants(rng, n: int, stratum: int, pre: dict) -> np.ndarray:
    """Tenant index per request (-1: no shared prefix). Rank ``k`` of
    the Zipf popularity is tenant ``perm[k]``."""
    with_prefix = int(round(pre["share"] * stratum))
    counts = zipf_counts(with_prefix, pre["count"], pre["zipf_s"])
    perm = rng.permutation(pre["count"])
    base = np.concatenate([np.full(c, perm[k]) for k, c in enumerate(counts)]
                          + [np.full(stratum - with_prefix, -1)])
    blocks = [rng.permutation(base) for _ in range(-(-n // stratum))]
    return np.concatenate(blocks)[:n]


def first_wave(rng, n: int, mix: dict, vocab: int) -> list:
    """The ``n`` requests the slots of a closed loop hold in its steady
    state. The set of (prompt, age, rest) sizes is the same for every
    seed (length-biased mid quantiles of the output distribution, ages
    at mid quantiles of each length, both paired by a fixed
    permutation); the seed draws which client sends which, and the
    token ids."""
    lengths = quantile_set(4096, mix["output"])
    cum = np.cumsum(lengths) / lengths.sum()
    mid = (np.arange(n) + 0.5) / n
    biased = lengths[np.minimum(np.searchsorted(cum, mid), len(lengths) - 1)]
    fixed = np.random.default_rng(0)
    age = np.floor(mid[fixed.permutation(n)] * biased).astype(np.int64)
    prompt = quantile_set(n, mix["prompt"])[fixed.permutation(n)]
    reqs = []
    for j in rng.permutation(n):
        tokens = rng.integers(0, vocab, int(prompt[j] + age[j])).tolist()
        reqs.append({"tokens": tokens,
                     "max_new_tokens": int(biased[j] - age[j]),
                     "prefix_id": None, "system_len": 0, "send_at": None,
                     "age": int(age[j])})
    return reqs


def request(plan: dict, i: int) -> dict:
    """The request of the ``i``-th send: the first wave's, then the
    pool's in turn."""
    first, pool = plan.get("first", []), plan["requests"]
    return first[i] if i < len(first) else pool[(i - len(first)) % len(pool)]


def max_context(mix: dict) -> int:
    """The longest prompt plus output the mix can send."""
    pre = mix.get("prefixes") or {"max": 0}
    return pre["max"] + mix["prompt"]["max"] + mix["output"]["max"]


def n_requests(mix: dict, cell: dict, max_slots: int, seconds: float) -> int:
    """Requests to draw: an open loop's arrivals over pre-roll and
    window; a closed loop's pool, which its clients cycle through."""
    if mix["loop"] == "open":
        return max(1, int(round(cell["rate_rps"]
                                * (cell["preroll_s"] + seconds))))
    return mix["stratum"] * mix["closed_pool_strata"]


def concurrency(mix: dict, max_slots: int) -> int:
    return int(math.ceil(mix["concurrency_per_slot"] * max_slots))


def draw(mix: dict, cell: dict, vocab: int, max_slots: int, seed: int,
         seconds: float) -> dict:
    """The run's requests: ``{"loop", "concurrency", "first", "requests":
    [{"tokens", "max_new_tokens", "prefix_id", "send_at"}]}``; ``send_at``
    is seconds after the traffic starts (open loop) or None (closed
    loop); ``first`` is the closed loop's first wave (empty unless the
    mix asks for one)."""
    rng = np.random.default_rng(seed)
    st = mix["stratum"]
    n = n_requests(mix, cell, max_slots, seconds)
    outputs = _stratified(rng, n, st, mix["output"])
    tails = _stratified(rng, n, st, mix["prompt"])
    pre = mix.get("prefixes")
    if pre:
        lens = np.rint(np.linspace(pre["min"], pre["max"], pre["count"]))
        lens = rng.permutation(lens.astype(np.int64))
        systems = [rng.integers(0, vocab, int(k)).tolist() for k in lens]
        tenant = _tenants(rng, n, st, pre)
    else:
        tenant = np.full(n, -1)
    send_at = [None] * n
    if mix["loop"] == "open":
        rate = cell["rate_rps"]
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q) / rate)
        send_at = np.cumsum(gaps).tolist()
    reqs = []
    for i in range(n):
        t = int(tenant[i])
        tokens = rng.integers(0, vocab, int(tails[i])).tolist()
        system = systems[t] if t >= 0 else []
        body = {"tokens": system + tokens, "max_new_tokens": int(outputs[i]),
                "prefix_id": f"tenant{t}" if t >= 0 else None,
                "system_len": len(system), "send_at": send_at[i]}
        reqs.append(body)
    first = (first_wave(rng, max_slots, mix, vocab)
             if mix.get("first_wave") == "equilibrium" else [])
    return {"loop": mix["loop"], "first": first,
            "concurrency": (concurrency(mix, max_slots)
                            if mix["loop"] == "closed" else None),
            "requests": reqs}
