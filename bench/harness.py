"""One run of one cell, from the seed to the result line.

``run_cell`` is every phase of ``bench/run.py`` after the look for the
chip: weights from the seed, the prune through ``MosaicPipeline``, the
selection the reference needs, the engine behind the gateway, warm-up,
the load generator's child process, the measured window (and, with
``trace``, a profiled part of it), the metrics, and the comparison with
the reference that decides ``correct``. Tests drive it on the CPU.
"""
from __future__ import annotations

import asyncio
import gc
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import devtrace, layer_metrics, model, models, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GB = 1e9


def load_spec(workload: str, benchmark: dict) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration,
    traffic mix and cell files, the model module its configuration
    names, and the metrics it reports."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]

    def here(m):
        return workload in m.get("workloads", [workload])
    e2e = [m for m in benchmark["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    conf = model.load_json("configs", w["config"])
    return {"name": workload, "chips": w["chips"], "conf": conf,
            "model": models.load(conf),
            "mix": model.load_json("traffic", w["traffic"]),
            "cell": model.load_json("cells", workload),
            "end_to_end": e2e, "per_layer": per_layer}


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else its fixed directory in the checkout), keeping
    every program, also the small ones."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Times (``time.monotonic``) at which JAX built a program."""

    def __init__(self):
        import jax
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.times.append((time.monotonic(), kw.get("fun_name", "?")))

    def between(self, t0: float, t1: float) -> list:
        """Names of the programs built in ``[t0, t1)``."""
        return [name for t, name in self.times if t0 <= t < t1]


class Recorder:
    """What the engine did, recorded from the benchmark's wrappers
    around its step callables, with host spans for the profiler.
    ``closing`` is set once the window has closed: from then on each
    request ends at its next token, so the run does not wait out the
    long requests still in flight (nothing after the window is
    measured)."""

    def __init__(self):
        self.bursts: list = []   # (t0, t1, active slots, ticks, sum ctx)
        self.chunks: list = []   # (t0, t1, start, tokens)
        self.closing = threading.Event()


def instrument(eng, rec: Recorder) -> None:
    """Wrap the engine's jitted steps in named host spans and count the
    work of each decode burst and prefill chunk. The engine looks these
    attributes up on the instance, so nothing in ``src/`` changes."""
    from jax.profiler import TraceAnnotation

    def span(name, fn):
        def wrapped(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    eng._prefill = span("bench.prefill_step", eng._prefill)
    eng._decode_sample = span("bench.decode_step", eng._decode_sample)
    eng._sample_first = span("bench.sample_first", eng._sample_first)
    burst, chunk = eng._decode_burst, eng._prefill_chunk

    def decode_burst(sched, pool, state, tick_state, *a, **k):
        active = sched.active()
        if rec.closing.is_set():
            for slot in active:
                req = slot.request
                req.max_new_tokens = min(req.max_new_tokens,
                                         len(slot.generated) + 1)
        ctx = sum(s.length for s in active)
        ticks0, t0 = tick_state["ticks"], time.monotonic()
        with TraceAnnotation("bench.decode_burst"):
            out = burst(sched, pool, state, tick_state, *a, **k)
        rec.bursts.append((t0, time.monotonic(), len(active),
                           tick_state["ticks"] - ticks0, ctx))
        return out

    def prefill_chunk(pool, slot, tables, state):
        start, t0 = slot.prefilled, time.monotonic()
        with TraceAnnotation("bench.prefill_chunk"):
            out = chunk(pool, slot, tables, state)
        rec.chunks.append((t0, time.monotonic(), start,
                           slot.prefilled - start))
        return out

    eng._decode_burst = decode_burst
    eng._prefill_chunk = prefill_chunk


class GatewayThread:
    """The program's ``Gateway`` on port 0, its asyncio loop in a thread
    of this process (the engine runs in the bridge's own thread)."""

    def __init__(self, eng):
        self.eng = eng
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="bench-gateway", daemon=True)
        self.gw = None

    def start(self) -> int:
        from repro.serve.gateway import Gateway
        self.thread.start()
        self.gw = asyncio.run_coroutine_threadsafe(
            Gateway(self.eng, port=0).start(), self.loop).result(60)
        return self.gw.port

    def close(self, timeout: float):
        """Stop intake, let the engine finish its work; returns the
        engine's ``(finished, stats)``."""
        try:
            return asyncio.run_coroutine_threadsafe(
                self.gw.close(), self.loop).result(timeout)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout)
            self.loop.close()


def warm_up(eng, serve) -> None:
    """Build every program the cell's traffic can reach before the
    window: the engine's jitted steps, by serving one request whose
    first decode burst is the longest; the host-side reads of the first
    token at each offset of a prefill chunk; and the stacking of each
    burst length's tokens for its one host sync."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serve.scheduler import Request
    eng.run([Request(uid=0, prompt=[1] * 3, max_new_tokens=9)])
    pool = jax.eval_shape(lambda: T.init_paged_pool(
        eng.cfg, serve.arena_blocks, serve.block_size, serve.cache_dtype))
    chunk = serve.prefill_chunk
    s = jax.ShapeDtypeStruct
    logits, _ = jax.eval_shape(
        eng._prefill, eng.params, pool, s((1, chunk), jnp.int32),
        s((1, serve.blocks_per_seq), jnp.int32), s((1,), jnp.int32),
        s((1,), jnp.int32))
    # the engine's arrays are uncommitted: so are these, or the
    # programs built here would not be the ones it looks up
    row = jnp.zeros(logits.shape, logits.dtype)
    for n in range(1, chunk + 1):
        row[0, n - 1, :]
    steps = [jnp.full((serve.max_slots,), i, jnp.int32) for i in range(8)]
    for k in range(1, 9):
        jnp.stack(steps[:k])
    jax.block_until_ready(row)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) sort
    last."""
    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])


def _in(t, w) -> bool:
    return w[0] <= t < w[1]


def end_to_end(records: list, window: tuple, loop: str) -> dict:
    """The client-side numbers of the window from the load generator's
    records: TTFT of every request due in it (a request that failed
    counts as infinitely late), TPOT of every request with two tokens in
    it, and the tokens received in it."""
    seconds = window[1] - window[0]
    ttft, tpot, n_tok = [], [], 0
    due = [r for r in records if _in(r["due"], window)] if loop == "open" \
        else [r for r in records if r["sent"] and _in(r["sent"], window)]
    for r in records:
        inside = [(t, n) for t, n in r["bursts"] if _in(t, window)]
        k = sum(n for _, n in inside)
        n_tok += k
        if k >= 2:
            tpot.append((inside[-1][0] - inside[0][0]) / (k - 1))
    for r in due:
        ok = r["status"] == "done" and r["bursts"]
        ttft.append(r["bursts"][0][0] - r["due"] if ok else math.inf)
    failed = sum(r["status"] != "done" for r in due)
    return {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "tpot_p90_ms": percentile(tpot, 90) * 1e3,
            "tokens_per_s": n_tok / seconds,
            "attempted": len(due), "failed": failed,
            "n_tpot": len(tpot)}


def _sample(records: list, seed: int, k: int) -> list:
    """The requests the reference checks: the longest served one and
    ``k - 1`` others drawn from the seed."""
    done = [r for r in records if r["status"] == "done" and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["tokens"]), -r["i"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[int(j)] for j in pick]


class Served:
    """A pruned model served behind the gateway, ready for traffic."""


def prepare(spec: dict, seed: int, *, cache: bool = True,
            hooks: dict = None, log=print) -> Served:
    """Set-up: weights from ``seed``, the prune, the selection the
    reference needs, the engine behind the gateway, warm-up."""
    import jax
    hooks = hooks or {}
    st = Served()
    conf, mod = spec["conf"], spec["model"]
    if cache:
        log(f"compile cache: {enable_compile_cache()}")
    st.compiles = CompileLog()
    st.devices = jax.devices()
    st.dev = st.devices[0]

    cfg = mod.program_config(conf)
    dense = mod.init_params(conf, seed)
    jax.block_until_ready(dense)
    from repro.core.pipeline import MosaicPipeline
    from repro.core.recipe import PruneRecipe
    recipe_d = dict(conf["recipe"], arch=conf["arch"])
    recipe_d["calibration"] = dict(recipe_d["calibration"], seed=int(seed))
    recipe = PruneRecipe.from_dict(recipe_d)
    t0 = time.monotonic()
    art = MosaicPipeline(recipe).run(dense, cfg)
    jax.block_until_ready(art.params)
    st.prune_s = time.monotonic() - t0
    t0 = time.monotonic()
    st.selection = mod.extract_selection(dense, art.params, recipe.block)
    log(f"pipeline: {st.prune_s:.3f} s; selection: "
        f"{time.monotonic() - t0:.3f} s; problems: "
        f"{len(st.selection['problems'])}")
    if "artifact" in hooks:             # the control: another path served
        art = hooks["artifact"](dense, cfg, recipe, art)
    del dense
    gc.collect()
    st.structure = mod.structure(art, recipe.block)

    from repro.serve.batching import ContinuousEngine
    from repro.serve.config import ServeConfig
    st.serve = ServeConfig(**conf["serve"])
    st.eng = ContinuousEngine.from_artifact(art, st.serve)
    st.art = art
    if "engine" in hooks:
        hooks["engine"](st.eng)
    st.rec = Recorder()
    instrument(st.eng, st.rec)
    warm_up(st.eng, st.serve)
    st.vocab = cfg.vocab
    st.gw = GatewayThread(st.eng)
    st.port = st.gw.start()
    return st


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, *, cache: bool = True, hooks: dict = None,
             log=print) -> dict:
    """One run; returns the result object of the last output line."""
    mix, cell = spec["mix"], spec["cell"]
    st = prepare(spec, seed, cache=cache, hooks=hooks, log=log)
    dev, devices, serve = st.dev, st.devices, st.serve
    plan = traffic.draw(mix, cell, st.vocab, serve.max_slots, seed, seconds)

    # ---- the child load generator, the window
    tmp = Path(tempfile.mkdtemp(prefix="bench-"))
    prof = devtrace.Profile(tmp / "trace") if trace else None
    client, window, in_use = drive(st.port, plan, cell, seconds, dev, tmp,
                                   prof, st.rec.closing)
    setup_s = window[0] - t_start
    st.gw.close(timeout=600)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    records = client["records"]
    n_compiles = st.compiles.between(*window)
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    log(f"compilations in the window: {len(n_compiles)} "
        f"{sorted(set(n_compiles))}")
    late = client["late_s"]
    log(f"load generator late: p50 {late['p50'] * 1e3:.3f} ms, p99 "
        f"{late['p99'] * 1e3:.3f} ms, max {late['max'] * 1e3:.3f} ms over "
        f"{late['n']} sends")

    e2e = end_to_end(records, window, plan["loop"])
    values = {"ttft_p90_ms": e2e["ttft_p90_ms"],
              "tpot_p90_ms": e2e["tpot_p90_ms"],
              "tokens_per_s": e2e["tokens_per_s"],
              "hbm_in_use_gb": in_use / GB, "setup_s": setup_s}
    log(f"window: {e2e['attempted']} requests attempted, {e2e['failed']} "
        f"failed, {e2e['n_tpot']} with two tokens in it")

    breakdown, device_extra = None, {}
    if trace:
        tr = prof.reduce()
        ctx = {"records": records, "window": window, "recorder": st.rec,
               "structure": st.structure, "serve": serve,
               "prune_s": st.prune_s,
               "trace": tr, "device_kind": dev.device_kind,
               "n_devices": 1}
        metrics = {}
        for m in spec["per_layer"]:
            v = layer_metrics.read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for note in ctx.get("notes", []):
            log(note)
        device_extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
        breakdown = tr["breakdown"]
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    shutil.rmtree(tmp, ignore_errors=True)

    # ---- free the program's state, then the reference
    selection = st.selection
    del st
    gc.collect()
    compared = check(spec, seed, selection, records, plan, serve, log,
                     on_gaps=(hooks or {}).get("gaps"))
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": bool(correct), "attempted": e2e["attempted"],
           "failed": e2e["failed"], "metrics": metrics,
           "device": dict({"platform": dev.platform,
                           "kind": dev.device_kind, "count": len(devices),
                           "memory_peak_bytes": int(peak)}, **device_extra)}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def drive(port: int, plan: dict, cell: dict, seconds: float, dev,
          tmp: Path, prof=None, closing=None) -> tuple:
    """Send ``plan``'s traffic from the child load generator: pre-roll,
    then the window of ``seconds`` (profiled in part where ``prof`` is
    given), then the drain, setting ``closing`` as the window closes.
    Returns the child's result, the window on
    ``time.monotonic`` and the device bytes serving keeps resident, read
    once every request has ended."""
    t_traffic = time.monotonic() + 0.5
    window = (t_traffic + cell["preroll_s"],
              t_traffic + cell["preroll_s"] + seconds)
    plan = dict(plan, port=port, t0=t_traffic, window=list(window),
                drain_s=cell["drain_s"])
    plan_path, result_path = tmp / "plan.json", tmp / "result.json"
    plan_path.write_text(json.dumps(plan))
    result_path.unlink(missing_ok=True)
    if closing is not None:
        closing.clear()
    child = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py"), str(plan_path),
         str(result_path)], stdin=subprocess.DEVNULL)
    try:
        if prof is not None:
            _sleep_until(window[0] + cell["trace_offset_s"])
            prof.start()
            _sleep_until(min(window[1], prof.t0 + cell["trace_s"]))
            prof.stop()
        _sleep_until(window[1])
        if closing is not None:
            closing.set()
        child.wait(timeout=cell["drain_s"] + 60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0 or not result_path.exists():
        raise RuntimeError(f"load generator exited {child.returncode}")
    return json.loads(result_path.read_text()), window, resident_bytes(dev)


def resident_bytes(dev, every: float = 0.05, reads: int = 100) -> int:
    """Device bytes in use with no step in flight: the served weights,
    the KV arena and whatever else serving keeps. Read once the last
    request has ended, when the engine waits for work, until two reads
    in a row agree."""
    last = None
    for _ in range(reads):
        gc.collect()
        now = (dev.memory_stats() or {}).get("bytes_in_use", 0)
        if now == last:
            break
        last = now
        time.sleep(every)
    return int(last)


def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def check(spec: dict, seed: int, selection: dict, records: list,
          plan: dict, serve, log, on_gaps=None) -> dict:
    """The comparison that decides ``correct``: every number compared,
    beside its limit. ``on_gaps``, where given, receives each checked
    request's gaps (``bench/control.py`` keeps them)."""
    import jax
    conf, cell, mod = spec["conf"], spec["cell"], spec["model"]
    stream_bad = sum(r["status"] == "done" and r["tokens"] != r["end"]["tokens"]
                     for r in records)
    sample = _sample(records, seed, cell["check_requests"])
    t0 = time.monotonic()
    dense = mod.init_params(conf, seed)
    jax.block_until_ready(dense)
    pad = min(serve.max_seq, -(-traffic.max_context(spec["mix"]) // 512) * 512)
    gaps = mod.served_gaps(
        conf, dense, selection,
        [(traffic.request(plan, r["i"])["tokens"], r["tokens"])
         for r in sample], pad_to=pad)
    widest, mean, n = _gap_stats(gaps)
    off = sum(int((g > 0).sum()) for g in gaps)
    log(f"reference: {len(sample)} requests, {n} served tokens, {off} not "
        f"its best, widest gap {widest!r}, {time.monotonic() - t0:.3f} s")
    if on_gaps is not None:
        on_gaps(gaps)
    compared = {
        "artifact_mismatch": {"value": len(selection["problems"]),
                              "limit": 0},
        "stream_mismatch": {"value": int(stream_bad), "limit": 0},
        "logit_gap_mean": {"value": mean,
                           "limit": cell["logit_gap_mean_limit"]},
    }
    for p in selection["problems"][:10]:
        log(f"artifact: {p}", file=sys.stderr)
    for name, c in compared.items():
        log(f"{name}: {c['value']!r} (limit {c['limit']!r})",
            file=sys.stderr)
    return compared


def _gap_stats(gaps: list) -> tuple:
    """(widest gap, mean gap, tokens) over every served token."""
    n = sum(len(g) for g in gaps)
    if not n:
        return math.inf, math.inf, 0
    return (float(max(g.max() for g in gaps if len(g))),
            float(sum(g.sum() for g in gaps) / n), n)
