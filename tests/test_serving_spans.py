"""Program spans and KV counters of the serving path.

A tiny paged config is served through ``ContinuousEngine.run`` and
through the ``Gateway`` (two requests each) under ``jax.profiler.trace``,
and the ``.xplane.pb`` is read back: every span of the engine and
gateway with its args, ``engine.burst.*`` inside ``engine.burst``, one
``engine.burst`` per decode burst, the gateway's spans on a line of
their own, ``decode.tokens`` against the burst spans' slots and ticks,
and the KV block-tick counters' bounds. Also: the block-tick sum against
a per-tick count of distinct blocks, exact KV counters on a run whose
requests take turns, ``slot_utilization`` from the decode counters, loop
state that does not grow per tick under ``serve_forever``, and the two
kernels' names in the lowered decode step."""
import asyncio
import json
import queue
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models import transformer as T
from repro.models.specs import (AttentionSpec, LayerSpec, MLPSpec,
                                ModelConfig)
from repro.serve.batching import ContinuousEngine
from repro.serve.config import ServeConfig
from repro.serve.engine import make_sparse_mlp_apply
from repro.serve.gateway import Gateway
from repro.serve.paging import block_ticks_used, make_paged_decode_step
from repro.serve.scheduler import Request
from repro.serve.sparse import pack_model

SERVE = dict(max_slots=2, max_seq=32, block_size=8, prefill_chunk=8,
             prefill_multiple=4, compute_dtype=jnp.float32,
             cache_dtype=jnp.float32)
# every span the program opens, with the args it carries
SPANS = {
    "engine.pump": {"submitted"},
    "engine.admit": {"admitted", "blocks_claimed"},
    "engine.prefill_chunk": {"uid", "start", "tokens"},
    "engine.first_token": {"uid"},
    "engine.cow_guard": {"copies"},
    "engine.burst": {"burst", "slots", "ticks", "ctx"},
    "engine.burst.prepare": set(),
    "engine.burst.dispatch": set(),
    "engine.burst.sync": set(),
    "engine.burst.emit": {"tokens"},
    "engine.wait": set(),
    "gateway.parse": {"uid"},
    "gateway.write": {"uid", "event"},
}


@pytest.fixture(scope="module")
def served():
    attn = AttentionSpec(n_q=4, n_kv=2, head_dim=16)
    cfg = ModelConfig(name="spn", d_model=64, vocab=256,
                      vocab_pad_multiple=16,
                      pattern=(LayerSpec(attn, MLPSpec(d_ff=128)),),
                      n_periods=2, scan_layers=False, remat=False)
    return T.init_model(jax.random.PRNGKey(0), cfg), cfg


def _count_bursts(eng) -> list:
    """Wrap the engine's decode burst; the list gets one entry per burst
    that decoded."""
    bursts, inner = [], eng._decode_burst

    def wrapped(*a, **k):
        out = inner(*a, **k)
        if out is not None:
            bursts.append(1)
        return out
    eng._decode_burst = wrapped
    return bursts


def _program_spans(trace_dir) -> list:
    """``(line, name, start_ns, dur_ns, args)`` of every program span; a
    line is keyed by its plane and position (threads share line names)."""
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("engine.", "gateway.")):
                    out.append(((plane.name, i), ev.name, ev.start_ns,
                                ev.duration_ns, dict(ev.stats)))
    return out


async def _post(port: int, body: dict) -> list:
    r, w = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    w.write(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
            b"Content-Length: %d\r\n\r\n" % len(payload) + payload)
    await w.drain()
    data = await r.read()
    w.close()
    await w.wait_closed()
    return [json.loads(line) for line in
            data.partition(b"\r\n\r\n")[2].splitlines() if line.strip()]


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """Both paths served under the profiler: the direct run on a thread
    of its own, the gateway's loop on this one."""
    params, cfg = served
    serve = ServeConfig(**SERVE)
    direct = ContinuousEngine(params, cfg, serve)
    gw_eng = ContinuousEngine(params, cfg, serve)
    bursts = {"direct": _count_bursts(direct), "gateway":
              _count_bursts(gw_eng)}
    reqs = [Request(uid=0, prompt=list(range(1, 12)), max_new_tokens=6),
            Request(uid=1, prompt=[5, 6, 7], max_new_tokens=9)]
    result = {}

    async def gateway():
        gw = await Gateway(gw_eng).start()
        events = await asyncio.gather(
            _post(gw.port, {"tokens": list(range(3, 14)),
                            "max_new_tokens": 5}),
            _post(gw.port, {"tokens": [9, 8, 7], "max_new_tokens": 7}))
        await gw.close()
        return events

    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        t = threading.Thread(
            target=lambda: result.update(run=direct.run(reqs)))
        t.start()
        t.join(timeout=300)
        assert not t.is_alive()
        events = asyncio.run(gateway())
    return {"spans": _program_spans(trace_dir), "engines": (direct, gw_eng),
            "bursts": sum(len(b) for b in bursts.values()),
            "events": events, "run": result["run"], "serve": serve}


def test_every_span_appears_with_its_args(traced):
    seen = {}
    for _, name, _, _, args in traced["spans"]:
        seen.setdefault(name, set()).update(args)
    assert set(seen) == set(SPANS)
    for name, want in SPANS.items():
        assert seen[name] == want, name
    for events in traced["events"]:
        assert events[-1]["event"] == "done"


def _within(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[2] + inner[3] <= outer[2] + outer[3])


def test_spans_nest_on_their_thread(traced):
    spans = traced["spans"]
    bursts = [s for s in spans if s[1] == "engine.burst"]
    chunks = [s for s in spans if s[1] == "engine.prefill_chunk"]
    for s in spans:
        if s[1].startswith("engine.burst."):
            assert any(_within(s, b) for b in bursts), s
        if s[1] == "engine.first_token":
            assert any(_within(s, c) for c in chunks), s


def test_one_burst_span_per_burst(traced):
    bursts = [s for s in traced["spans"] if s[1] == "engine.burst"]
    assert len(bursts) == traced["bursts"] > 0
    emits = [s for s in traced["spans"] if s[1] == "engine.burst.emit"]
    assert len(emits) == len(bursts)


def test_gateway_spans_sit_on_another_line(traced):
    lines = lambda prefix: {s[0] for s in traced["spans"]  # noqa: E731
                            if s[1].startswith(prefix)}
    assert lines("gateway.") and lines("engine.")
    assert not lines("gateway.") & lines("engine.")
    writes = [s for s in traced["spans"] if s[1] == "gateway.write"]
    delivered = sum(e.metrics.counters.get("gateway.events_delivered", 0)
                    for e in traced["engines"])
    assert len(writes) == delivered == sum(
        len(ev) for ev in traced["events"])


def test_decode_tokens_match_the_burst_spans(traced):
    bursts = [s[4] for s in traced["spans"] if s[1] == "engine.burst"]
    counters = [e.metrics.counters for e in traced["engines"]]
    assert sum(c["decode.tokens"] for c in counters) == sum(
        b["slots"] * b["ticks"] for b in bursts)
    assert sum(c["decode.ticks"] for c in counters) == sum(
        b["ticks"] for b in bursts)
    emits = [s[4]["tokens"] for s in traced["spans"]
             if s[1] == "engine.burst.emit"]
    assert sum(emits) == sum(c["decode.tokens"] for c in counters)


def test_kv_block_ticks_bounds(traced):
    arena = traced["serve"].arena_blocks
    for eng in traced["engines"]:
        c = eng.metrics.counters
        assert 0 < c["kv.block_ticks_used"] <= c["kv.block_ticks_reserved"]
        assert c["kv.block_ticks_reserved"] <= c["decode.ticks"] * arena
        assert 0 < eng.metrics.gauges["kv.blocks_used"] <= \
            eng.metrics.gauges["kv.blocks_reserved"] <= arena


def _distinct_per_tick(tables, lengths, held_extra, bs, ticks) -> list:
    """The loop version: distinct written blocks at each tick."""
    out = []
    for k in range(ticks):
        blocks = set(held_extra)
        for row, n in zip(tables, lengths):
            blocks.update(row[:(n + k) // bs + 1])
        out.append(len(blocks))
    return out


@pytest.mark.parametrize("seed", range(4))
def test_block_ticks_used_matches_a_per_tick_count(seed):
    rng = np.random.default_rng(seed)
    bs, ticks, n_slots = 8, int(rng.integers(1, 9)), int(rng.integers(0, 6))
    prefix = list(range(100, 103))              # shared, full blocks
    tables, lengths, nxt = [], [], 0
    for _ in range(n_slots):
        shared = prefix[:int(rng.integers(0, 4))]
        n = len(shared) * bs + int(rng.integers(1, 40))
        own = list(range(nxt, nxt + 10))
        nxt += 10
        tables.append(shared + own)
        lengths.append(n)
    extra = prefix + [200, 201]                 # a cached prefix, a prefill
    first = set(extra)
    for row, n in zip(tables, lengths):
        first.update(row[:n // bs + 1])
    want = _distinct_per_tick(tables, lengths, extra, bs, ticks)
    assert block_ticks_used(len(first), lengths, bs, ticks) == (
        sum(want), want[-1])
    assert block_ticks_used(len(first), lengths, bs, 0) == (0, len(first))


def test_kv_counters_exact_when_requests_take_turns(served):
    """Two requests on an arena that holds one at a time: the second is
    refused blocks until the first ends, and the counters are the two
    requests' sums. A request of prompt ``p`` and budget ``n`` decodes
    ``n - 1`` ticks, reserves ``ceil((p + n) / bs)`` blocks throughout
    and writes into ``(p + k) // bs + 1`` of them at tick ``k``."""
    params, cfg = served
    bs = 8
    serve = ServeConfig(**dict(SERVE, n_blocks=3))
    eng = ContinuousEngine(params, cfg, serve)
    shapes = [(11, 9), (5, 14)]
    reqs = [Request(uid=i, prompt=list(range(1, p + 1)), max_new_tokens=n)
            for i, (p, n) in enumerate(shapes)]
    fin, stats = eng.run(reqs)
    assert len(fin) == 2 and stats.rejected == 0
    c = eng.metrics.counters
    assert c["kv.admit_blocked"] >= 1
    assert c["kv.block_ticks_reserved"] == sum(
        (n - 1) * -(-(p + n) // bs) for p, n in shapes)
    assert c["kv.block_ticks_used"] == sum(
        (p + k) // bs + 1 for p, n in shapes for k in range(n - 1))


def _old_mean_recorder(eng) -> list:
    """Per tick, active / max_slots, as the loop once kept it."""
    util, inner = [], eng._decode_burst

    def wrapped(sched, pool, state, tick_state, *a, **k):
        n, ticks0 = len(sched.active()), tick_state["ticks"]
        out = inner(sched, pool, state, tick_state, *a, **k)
        util.extend([n / eng.max_slots] * (tick_state["ticks"] - ticks0))
        return out
    eng._decode_burst = wrapped
    return util


def test_slot_utilization_equals_the_mean_over_ticks(served):
    params, cfg = served
    eng = ContinuousEngine(params, cfg, ServeConfig(**SERVE))
    for _ in range(2):              # the registry's counters carry over
        util = _old_mean_recorder(eng)
        reqs = [Request(uid=i, prompt=list(range(1, 2 + 3 * i)),
                        max_new_tokens=4 + 5 * i) for i in range(3)]
        _, stats = eng.run(reqs)
        del eng._decode_burst       # the class's own again
        assert stats.ticks == len(util) > 0
        assert stats.slot_utilization == pytest.approx(np.mean(util),
                                                       rel=1e-12)


def test_loop_state_does_not_grow_per_tick(served):
    params, cfg = served
    eng = ContinuousEngine(params, cfg, ServeConfig(**SERVE))
    sizes, inner = [], eng._decode_burst

    def wrapped(sched, pool, state, tick_state, *a, **k):
        sizes.append({key: len(v) for key, v in tick_state.items()
                      if hasattr(v, "__len__")})
        return inner(sched, pool, state, tick_state, *a, **k)
    eng._decode_burst = wrapped
    inbox, stop = queue.Queue(), threading.Event()
    for i in range(3):
        inbox.put(Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=20))
    out = {}
    t = threading.Thread(target=lambda: out.update(r=eng.serve_forever(
        inbox, lambda ev: None, stop=stop, max_burst=2)))
    t.start()
    stop.set()
    t.join(timeout=300)
    assert not t.is_alive()
    fin, stats = out["r"]
    assert len(fin) == 3 and stats.ticks >= 20 and len(sizes) >= 10
    assert all(s == sizes[0] for s in sizes)
    for buf in eng.metrics.series.values():
        assert len(buf) <= eng.metrics.capacity


def test_lowered_decode_step_names_both_kernels(served):
    params, cfg = served
    packed = pack_model(params, cfg, block=16)
    assert packed
    step = make_paged_decode_step(
        cfg, jnp.float32, make_sparse_mlp_apply(packed, True, False, "none"),
        paged_kernel=True)
    pool = T.init_paged_pool(cfg, 8, 8, jnp.float32)
    text = jax.jit(step).lower(
        params, pool, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32)
    ).as_text(debug_info=True)
    assert '"blocksparse/pallas_call"' in text
    assert '"paged_attention/pallas_call"' in text
