"""Continuous-batching generation engine over a slot-based KV pool.

One jitted decode step runs every tick over *all* slots of the KV pool,
and prefills are admitted between ticks into whatever slots are free —
so requests of different lengths enter and leave the batch continuously
without recompiling the decode step. Prompts are right-padded to a
bucket multiple to bound prefill retraces; padded positions are masked
by the per-slot length and overwritten as the sequence grows.

Two pool backends, selected by ``ServeConfig.block_size``:

- **contiguous** (``block_size=None``): one ``(max_slots, max_seq)``
  region per slot, per-slot lengths as the vector ``cache_index``.
- **paged** (``block_size=N``): fixed-size KV blocks in one shared
  arena (``transformer.init_paged_pool``), per-request block tables
  threaded through the jitted steps, a host-side block allocator with
  refcounts + copy-on-write, prefix sharing keyed on
  ``Request.prefix_id``, and chunked prefill — long prompts enter the
  cache in block-multiple chunks that interleave with decode ticks
  instead of stalling them (``repro.serve.paging``).

With a ``packed`` plan (``sparse.pack_model`` on a Mosaic-pruned model)
the MLP projections run through the Pallas block-sparse kernel inside
the same jitted steps — the pruned fast path in the serving hot loop —
on either backend.

The tick loop is driven through a small *feed* seam: ``run`` wires in a
batch feed (all requests pre-submitted, loop exits when drained) while
``serve_forever`` wires in a live feed pulling from a thread-safe
submission queue and emitting per-token events — the streaming gateway
(``repro.serve.gateway``) runs this in a background thread. Both paths
execute the identical admission/prefill/decode code, so gateway outputs
are token-identical to driving the engine directly. Admission *order*
is a pluggable :mod:`~repro.serve.policies` policy selected by
``ServeConfig.scheduler`` (``fifo`` default, behavior-preserving).
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import time
from collections import Counter
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.specs import AttentionSpec, ModelConfig
from repro.serve import metrics as M
from repro.serve.config import ServeConfig
from repro.serve.engine import (_legacy_serve_config, make_prefill_step,
                                make_serve_step, make_sparse_mlp_apply,
                                request_key, sample_tokens)
from repro.serve.metrics import (MetricsRegistry,  # noqa: F401 (re-export)
                                 latency_percentiles)
from repro.serve.paging import (BlockAllocator, PrefixCache,
                                block_ticks_used, make_paged_decode_step,
                                make_paged_prefill_step)
from repro.serve.scheduler import Scheduler


@dataclasses.dataclass
class ServeStats:
    ticks: int
    wall_s: float
    generated_tokens: int
    tokens_per_s: float
    slot_utilization: float     # mean active/max_slots over decode ticks
    prefills: int               # completed prompt prefills
    rejected: int
    prefill_chunks: int = 0     # jitted prefill launches (>= prefills
    #                             when chunked prefill splits prompts)
    peak_concurrency: int = 0   # max sequences holding cache at once
    prompt_blocks_shared: int = 0   # paged: prefix-cache block hits
    prefix_hit_rate: float = 0.0    # shared / shareable prompt blocks
    reject_reasons: dict = dataclasses.field(default_factory=dict)
    #                           # {"prompt_too_long": n, ...}

    def to_dict(self) -> dict:
        """JSON-safe dump (the gateway's /metrics stats block)."""
        return dataclasses.asdict(self)


class _BatchFeed:
    """Default feed: every request was pre-submitted by ``run``; the
    loop exits when the scheduler drains, and idles by sleeping until
    the next future arrival (PR 6 semantics, bitwise-preserving)."""

    def pump(self, sched, now: float) -> int:
        return 0

    def drained(self) -> bool:
        return True

    def wait(self, sched, clock) -> None:
        if sched.prefilling:
            return                      # chunked prefill still progresses
        arrival = sched.next_arrival()
        if arrival is not None:
            delay = arrival - clock()
            if delay > 0:
                time.sleep(delay)

    def emit_token(self, slot, token: int, now: float) -> None:
        pass

    def emit_finished(self, fin) -> None:
        pass

    def emit_rejected(self, rej) -> None:
        pass


class _QueueFeed(_BatchFeed):
    """Live feed: requests arrive on a thread-safe ``queue.Queue`` and
    events stream out through ``emit`` — the gateway's bridge into the
    tick loop. ``stop`` (a ``threading.Event``) ends the loop once the
    inbox and scheduler are both drained."""

    def __init__(self, inbox: queue_mod.Queue, emit: Callable,
                 stop=None, poll_s: float = 0.002):
        self.inbox = inbox
        self.emit = emit
        self.stop = stop
        self.poll_s = poll_s
        self._staged: list = []

    def pump(self, sched, now: float) -> int:
        """Move the inbox into the scheduler; returns how many moved."""
        n = 0
        while True:
            if self._staged:
                req = self._staged.pop(0)
            else:
                try:
                    req = self.inbox.get_nowait()
                except queue_mod.Empty:
                    break
            # the submission's arrival is its intake time on the
            # engine clock — queue-wait metrics start here
            req.arrival = now
            sched.submit(req)
            n += 1
        return n

    def drained(self) -> bool:
        return (self.stop is not None and self.stop.is_set()
                and not self._staged and self.inbox.empty())

    def wait(self, sched, clock) -> None:
        if sched.prefilling:
            return
        try:        # block briefly for the next submission, don't spin
            self._staged.append(self.inbox.get(timeout=self.poll_s))
        except queue_mod.Empty:
            pass

    def emit_token(self, slot, token: int, now: float) -> None:
        self.emit(("token", slot.request.uid,
                   len(slot.generated) - 1, token))

    def emit_finished(self, fin) -> None:
        self.emit(("finished", fin))

    def emit_rejected(self, rej) -> None:
        self.emit(("rejected", rej))


class ContinuousEngine:
    """Slot-pool engine: FIFO admission, per-tick batched decode,
    immediate slot reuse after eviction. Construct with a
    :class:`~repro.serve.config.ServeConfig` (the legacy kwarg surface
    is a deprecation shim)."""

    def __init__(self, params, cfg: ModelConfig, serve=None,
                 max_slots: Optional[int] = None,
                 max_seq: Optional[int] = None, compute_dtype=None,
                 cache_dtype=None, packed: Optional[dict] = None,
                 prefill_multiple: Optional[int] = None,
                 group_experts: Optional[bool] = None):
        if isinstance(serve, int):  # legacy positional (max_slots, max_seq)
            if max_slots is not None and max_seq is None:
                max_seq = max_slots
            max_slots, serve = serve, None
        if serve is None:
            serve = _legacy_serve_config(
                "ContinuousEngine", max_slots, max_seq, compute_dtype,
                cache_dtype, prefill_multiple, group_experts)
        if cfg.scan_layers:
            raise ValueError("continuous batching needs an unrolled config "
                             "(cfg.replace(scan_layers=False))")
        hybrid = any(not isinstance(cfg.layer(i).mixer, AttentionSpec)
                     for i in range(cfg.n_layers))
        if serve.prefill_multiple != 1 and hybrid:
            # attention masks padded prefill positions via the per-slot
            # length; an SSM integrates them into its recurrent state
            raise ValueError("SSM/hybrid mixers need unpadded prefills: "
                             "pass prefill_multiple=1")
        if serve.paged and hybrid:
            raise ValueError("paged KV pools support attention-only "
                             "configs (SSM state is recurrent, not "
                             "positional)")
        self.serve = serve
        self.params = params
        self.cfg = cfg
        self.max_slots = serve.max_slots
        self.max_seq = serve.max_seq
        self.cache_dtype = serve.cache_dtype
        self.prefill_multiple = serve.prefill_multiple
        # per-stage observability: request latencies + tick gauges land
        # here (host-side ring buffers; the gateway's /metrics source)
        self.metrics = MetricsRegistry()
        mlp_apply = (make_sparse_mlp_apply(packed, serve.group_experts,
                                           serve.ragged_moe, serve.quant)
                     if packed else None)
        if serve.paged:
            self._prefill = jax.jit(make_paged_prefill_step(
                cfg, serve.compute_dtype, mlp_apply))
            decode = make_paged_decode_step(
                cfg, serve.compute_dtype, mlp_apply,
                paged_kernel=serve.paged_kernel)
            self._copy_block = jax.jit(T.copy_pool_block)
        else:
            self._prefill = jax.jit(make_prefill_step(
                cfg, serve.compute_dtype, mlp_apply))
            decode = make_serve_step(cfg, serve.compute_dtype, mlp_apply)
            self._write = jax.jit(T.write_cache_slot)

        # one fused dispatch per tick: decode + sample on device, only
        # the (max_slots,) sampled tokens come back to the host.
        # Sampling state is *traced* — per-slot base keys, sample
        # counts, and a per-slot temperature vector — so mixed-
        # temperature batches never retrace the decode step.
        def decode_sample(params, pool, tokens, lengths, bases, counts,
                          temps, *tables):
            logits, pool = decode(params, pool, tokens, lengths, *tables)
            keys = jax.vmap(jax.random.fold_in)(bases, counts)
            return sample_tokens(logits, keys, temps, cfg.vocab), pool
        self._decode_sample = jax.jit(decode_sample)

        def first_sample(logits_row, base, temp):
            key = jax.random.fold_in(base, 0)
            return sample_tokens(logits_row[None], key[None], temp[None],
                                 cfg.vocab)[0]
        self._first_sample = jax.jit(first_sample)

    @classmethod
    def from_artifact(cls, artifact, serve=None,
                      max_seq: Optional[int] = None, *,
                      sparse: bool = True, **kw) -> "ContinuousEngine":
        """Serve a loaded :class:`~repro.core.artifact.PrunedArtifact`:
        the saved block plans are rehydrated into the jitted hot loop —
        no ``pack_model`` at startup. Expert plan stacks keep their
        saved ``group`` flag, so MoE bundles serve through the grouped
        one-launch kernel with zero repacking. ``serve`` is a
        :class:`ServeConfig` (two ints are the deprecated
        ``max_slots, max_seq``)."""
        if isinstance(serve, int):      # legacy (max_slots, max_seq)
            kw["max_slots"], serve = serve, None
        if max_seq is not None:
            kw["max_seq"] = max_seq
        packed = artifact.packed if sparse else None
        return cls(artifact.params, artifact.cfg, serve,
                   packed=packed or None, **kw)

    # ------------------------------------------------------------ pieces

    def _bucket(self, n: int) -> int:
        m = self.prefill_multiple
        return min(-(-n // m) * m, self.max_seq)

    def _request_sampling(self, slot, state, default_temp, run_seed):
        """Bind the request's sampling stream to its slot."""
        req = slot.request
        t = req.temperature if req.temperature is not None else default_temp
        state["bases"][slot.index] = np.asarray(
            request_key(req.seed, req.uid, run_seed))
        state["temps"][slot.index] = t

    def _sample_first(self, logits_row, slot, state):
        """Sample the request's first token from its prefill logits."""
        with M.span("engine.first_token", uid=slot.request.uid):
            return int(self._first_sample(
                logits_row, jnp.asarray(state["bases"][slot.index]),
                jnp.asarray(state["temps"][slot.index], jnp.float32)))

    # ----------------------------------------------------------- prefill

    def _prefill_slot(self, pool, slot, state):
        """Contiguous pool: prefill one request into its slot; returns
        (pool, first_token)."""
        prompt = np.asarray(slot.request.prompt, np.int32)
        s0 = len(prompt)
        bucket = self._bucket(s0)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :s0] = prompt
        row = T.init_cache(self.cfg, 1, self.max_seq, self.cache_dtype)
        logits, row = self._prefill(self.params, jnp.asarray(padded), row)
        pool = self._write(pool, row, jnp.int32(slot.index))
        return pool, self._sample_first(logits[0, s0 - 1, :], slot, state)

    def _prefill_chunk(self, pool, slot, tables, state):
        """Paged pool: feed the next chunk of the request's prompt in;
        returns (pool, first_token_or_None)."""
        serve = self.serve
        prompt = slot.request.prompt
        s0 = len(prompt)
        start = slot.prefilled
        chunk = serve.prefill_chunk or (s0 - start)
        end = min(start + chunk, s0)
        n = end - start
        with M.span("engine.prefill_chunk", uid=slot.request.uid,
                    start=start, tokens=n):
            bucket = self._bucket(n)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt[start:end]
            logits, pool = self._prefill(
                self.params, pool, jnp.asarray(padded),
                jnp.asarray(tables[slot.index:slot.index + 1]),
                jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32))
            slot.prefilled = end
            if end < s0:
                return pool, None
            return pool, self._sample_first(logits[0, n - 1, :], slot, state)

    # -------------------------------------------------------------- run

    def _wire(self, sched, feed) -> None:
        """Route scheduler lifecycle events into metrics + the feed."""
        def on_finish(fin):
            M.observe_finished(self.metrics, fin)
            feed.emit_finished(fin)

        def on_reject(rej):
            self.metrics.count(f"requests.rejected.{rej.reason}")
            feed.emit_rejected(rej)

        sched.on_token = feed.emit_token
        sched.on_finish = on_finish
        sched.on_reject = on_reject

    def _sampling_state(self, temperature: float, seed: int) -> dict:
        return {
            "bases": np.zeros((self.max_slots, 2), np.uint32),
            "temps": np.zeros((self.max_slots,), np.float32),
            "default_temp": float(temperature), "run_seed": int(seed),
        }

    def run(self, requests, temperature: float = 0.0, seed: int = 0,
            max_ticks: Optional[int] = None, max_burst: int = 8):
        """Serve ``requests`` to completion.

        Arrivals are seconds on the wall clock starting when ``run`` is
        called (``Request.arrival=0`` = available immediately). Returns
        ``(finished, stats)`` where ``finished`` is uid-sorted
        ``scheduler.Finished`` records.

        ``temperature`` and ``seed`` are *defaults* for requests that
        don't carry their own ``Request.temperature`` / ``Request.seed``
        — sampling knobs are per-request, and a request with its own
        seed samples the same stream regardless of batch composition.

        Decode runs in bursts of up to ``max_burst`` ticks that chain
        the sampled tokens on-device, so the hot loop stays async and
        only syncs with the host scheduler once per burst. Bursts never
        exceed the smallest remaining per-slot budget, so the only
        waste is an EOS landing mid-burst (those tokens are dropped and
        the slot frees at the burst boundary); the generated sequences
        are identical to tick-by-tick decoding.
        """
        sched = Scheduler(self.max_slots, self.max_seq,
                          policy=self.serve.scheduler)
        feed = _BatchFeed()
        self._wire(sched, feed)
        for r in requests:
            sched.submit(r)
        state = self._sampling_state(temperature, seed)
        if self.serve.paged:
            return self._run_paged(sched, state, max_ticks, max_burst, feed)
        return self._run_contiguous(sched, state, max_ticks, max_burst,
                                    feed)

    def serve_forever(self, inbox: queue_mod.Queue, emit: Callable,
                      *, stop, temperature: float = 0.0, seed: int = 0,
                      max_burst: int = 8, poll_s: float = 0.002):
        """Drive the tick loop off a live submission queue (the gateway
        front door runs this in a background thread).

        ``inbox`` is a thread-safe ``queue.Queue`` of
        :class:`~repro.serve.scheduler.Request`; each submission's
        ``arrival`` is stamped with its intake time on the engine
        clock. ``emit(event)`` is called from the engine thread with
        ``("token", uid, index, token)``, ``("finished", Finished)``
        and ``("rejected", Rejection)`` events, in generation order per
        request (tokens surface at burst boundaries, up to
        ``max_burst`` at a time). ``stop`` is a ``threading.Event``:
        once set, the loop finishes the work it has, drains the inbox,
        and returns ``(finished, stats)`` exactly like :meth:`run`.

        Admission order, sampling streams, and every jitted step are
        shared with :meth:`run` — a request submitted here generates
        the same tokens it would generate driving the engine directly.
        """
        sched = Scheduler(self.max_slots, self.max_seq,
                          policy=self.serve.scheduler)
        feed = _QueueFeed(inbox, emit, stop=stop, poll_s=poll_s)
        self._wire(sched, feed)
        state = self._sampling_state(temperature, seed)
        if self.serve.paged:
            return self._run_paged(sched, state, None, max_burst, feed)
        return self._run_contiguous(sched, state, None, max_burst, feed)

    def _decode_burst(self, sched, pool, state, tick_state, max_ticks,
                      max_burst, tables=None):
        """One decode burst over the active slots (both backends);
        returns the updated pool, or None when there is nothing to
        decode."""
        active = sched.active()
        if not active:
            return None
        t_burst = time.perf_counter()
        remaining = min(
            min(s.request.max_new_tokens - len(s.generated),
                self.max_seq - s.length) for s in active)
        burst = max(1, min(max_burst, remaining))
        if max_ticks is not None:
            burst = min(burst, max_ticks - tick_state["ticks"])
        tick_state["bursts"] += 1
        with M.span("engine.burst", burst=tick_state["bursts"],
                    slots=len(active), ticks=burst,
                    ctx=sum(s.length for s in active)):
            with M.span("engine.burst.prepare"):
                tokens_in = np.zeros((self.max_slots, 1), np.int32)
                lengths = np.zeros((self.max_slots,), np.int32)
                counts = np.zeros((self.max_slots,), np.int32)
                for s in active:
                    tokens_in[s.index, 0] = s.last_token
                    lengths[s.index] = s.length
                    counts[s.index] = len(s.generated)
                extra = ((jnp.asarray(tables),) if tables is not None
                         else ())
                toks_dev = jnp.asarray(tokens_in)
                lens_dev = jnp.asarray(lengths)
                counts_dev = jnp.asarray(counts)
                bases_dev = jnp.asarray(state["bases"])
                temps_dev = jnp.asarray(state["temps"])
            with M.span("engine.burst.dispatch"):
                steps = []
                for _ in range(burst):
                    sampled, pool = self._decode_sample(
                        self.params, pool, toks_dev, lens_dev, bases_dev,
                        counts_dev, temps_dev, *extra)
                    steps.append(sampled)
                    toks_dev = sampled[:, None]
                    lens_dev = lens_dev + 1
                    counts_dev = counts_dev + 1
            with M.span("engine.burst.sync"):
                host = np.asarray(jnp.stack(steps))    # one sync per burst
            with M.span("engine.burst.emit", tokens=burst * len(active)):
                for k in range(burst):
                    sched.decoded({s.index: host[k, s.index]
                                   for s in active}, tick_state["clock"]())
                    tick_state["ticks"] += 1
                burst_s = time.perf_counter() - t_burst
                m = self.metrics
                m.observe("tick.active_slots", len(active))
                m.observe("tick.prefill_backlog",
                          len(sched.prefilling) + len(sched.queue))
                if burst_s > 0:
                    m.gauge("tick.tokens_per_s",
                            burst * len(active) / burst_s)
                m.count("decode.ticks", burst)
                m.count("decode.tokens", burst * len(active))
        return pool

    def _tick_state(self, clock) -> dict:
        """Per-run loop state; ``tokens0`` is where the registry's
        ``decode.tokens`` stood when the run began."""
        return {"ticks": 0, "bursts": 0, "peak": 0, "clock": clock,
                "tokens0": self.metrics.counters.get("decode.tokens", 0.0)}

    def _stats(self, sched, tick_state, wall, prefills, chunks):
        finished = sorted(sched.finished, key=lambda f: f.request.uid)
        n_tok = sum(len(f.tokens) for f in finished)
        shared = sum(f.prompt_blocks_shared for f in finished)
        shareable = 0
        if self.serve.paged:
            bs = self.serve.block_size
            shareable = sum((len(f.request.prompt) - 1) // bs
                            for f in finished
                            if f.request.prefix_id is not None)
        ticks = tick_state["ticks"]
        tokens = (self.metrics.counters.get("decode.tokens", 0.0)
                  - tick_state["tokens0"])
        return finished, ServeStats(
            ticks=ticks, wall_s=wall,
            generated_tokens=n_tok,
            tokens_per_s=n_tok / wall if wall > 0 else 0.0,
            slot_utilization=(tokens / (ticks * self.max_slots)
                              if ticks else 0.0),
            prefills=prefills, rejected=len(sched.rejected),
            prefill_chunks=chunks,
            peak_concurrency=tick_state["peak"],
            prompt_blocks_shared=shared,
            prefix_hit_rate=shared / shareable if shareable else 0.0,
            reject_reasons=dict(Counter(r.reason
                                        for r in sched.rejected)))

    # ------------------------------------------------- contiguous backend

    def _run_contiguous(self, sched, state, max_ticks, max_burst, feed):
        pool = T.init_cache_pool(self.cfg, self.max_slots, self.max_seq,
                                 self.cache_dtype)
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0  # noqa: E731
        tick_state = self._tick_state(clock)
        prefills = 0

        while True:
            feed.pump(sched, clock())
            if not sched.has_work():
                if feed.drained():
                    break
                feed.wait(sched, clock)
                continue
            if max_ticks is not None and tick_state["ticks"] >= max_ticks:
                break
            for slot in sched.admissions(clock()):
                self._request_sampling(slot, state, state["default_temp"],
                                       state["run_seed"])
                pool, tok = self._prefill_slot(pool, slot, state)
                prefills += 1
                sched.started(slot, tok, clock())
            tick_state["peak"] = max(tick_state["peak"],
                                     sched.concurrency())
            new_pool = self._decode_burst(sched, pool, state, tick_state,
                                          max_ticks, max_burst)
            if new_pool is None:
                feed.wait(sched, clock)     # future arrivals / live inbox
                continue
            pool = new_pool

        return self._stats(sched, tick_state, clock(), prefills, prefills)

    # ------------------------------------------------------ paged backend

    def _blocks_for(self, req, prefix: PrefixCache) -> int:
        """Blocks a request must *own*: enough for every KV position it
        can write (prompt + budget, capped at max_seq), minus blocks a
        prefix-cache hit would map in, plus one copy-on-write reserve
        when entering on shared blocks. Reserved in full at admission,
        so neither decode nor COW can run out of blocks mid-request."""
        bs = self.serve.block_size
        cap = min(len(req.prompt) + req.max_new_tokens, self.max_seq)
        shared = len(prefix.match(req.prefix_id, req.prompt))
        own = -(-cap // bs) - shared
        return own + (1 if shared else 0)

    def _run_paged(self, sched, state, max_ticks, max_burst, feed):
        serve = self.serve
        bs = serve.block_size
        alloc = BlockAllocator(serve.arena_blocks, bs)
        prefix = PrefixCache(alloc)
        pool = T.init_paged_pool(self.cfg, serve.arena_blocks, bs,
                                 self.cache_dtype)
        tables = np.full((self.max_slots, serve.blocks_per_seq),
                         alloc.scratch, np.int32)
        slot_blocks: dict[int, list] = {}
        # slot index -> block pre-claimed at admission for copy-on-write
        # (only slots that entered on shared prefix blocks have one)
        slot_reserve: dict[int, int] = {}
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0  # noqa: E731
        tick_state = self._tick_state(clock)
        prefills = chunks = 0

        # blocks are *reserved* inside the admission gate — several
        # requests can be admitted in one scheduler call, so checking
        # n_free without claiming would over-admit against the same
        # free blocks
        pending: dict[int, tuple] = {}      # uid -> (shared, owned)

        def can_admit(req):
            if req.uid in pending:
                return True
            shared = prefix.match(req.prefix_id, req.prompt)
            need = self._blocks_for(req, prefix)
            if need > alloc.n_free:
                self.metrics.count("kv.admit_blocked")
                return False
            if shared:
                alloc.retain(shared)
            pending[req.uid] = (list(shared), alloc.alloc(need))
            return True

        def release_if_finished(slot):
            if (slot.index not in sched.slots
                    and slot.index not in sched.prefilling):
                blocks = slot_blocks.pop(slot.index, None)
                if blocks:
                    alloc.release(blocks)
                reserve = slot_reserve.pop(slot.index, None)
                if reserve is not None:
                    alloc.release([reserve])    # COW never fired
                tables[slot.index, :] = alloc.scratch

        while True:
            with M.span("engine.pump") as sp:
                sp.set_metadata(submitted=feed.pump(sched, clock()))
            if not sched.has_work():
                if feed.drained():
                    break
                with M.span("engine.wait"):
                    feed.wait(sched, clock)
                continue
            if max_ticks is not None and tick_state["ticks"] >= max_ticks:
                break

            # ---- admissions: map shared prefix blocks + claim the rest
            with M.span("engine.admit") as sp:
                admitted = sched.admissions(clock(), can_admit)
                if (not admitted and not sched.slots
                        and not sched.prefilling
                        and sched.head(clock()) is not None):
                    # head blocked with the pool idle: cached prefixes
                    # are the only block holders — drop them and retry;
                    # a head that still doesn't fit can never run
                    if len(prefix):
                        prefix.drop_all()
                        admitted = sched.admissions(clock(), can_admit)
                    head = sched.head(clock())
                    if (not admitted and head is not None
                            and not can_admit(head)):
                        sched.reject(sched.pop_head(), "insufficient_blocks",
                                     clock())
                        continue
                claimed = 0
                for slot in admitted:
                    req = slot.request
                    shared, owned = pending.pop(req.uid)
                    claimed += len(owned)
                    if shared:
                        # the last claimed block is the COW reserve: held
                        # outside the table until a shared-block write
                        # needs a private copy (or released at finish,
                        # unused)
                        slot_reserve[slot.index] = owned.pop()
                    row = shared + owned
                    tables[slot.index, :] = alloc.scratch
                    tables[slot.index, :len(row)] = row
                    slot_blocks[slot.index] = row
                    slot.shared_blocks = len(shared)
                    slot.prefilled = len(shared) * bs
                    self._request_sampling(slot, state,
                                           state["default_temp"],
                                           state["run_seed"])
                # reserved, not admitted
                for shared, owned in pending.values():
                    if shared:
                        alloc.release(shared)
                    alloc.release(owned)
                pending.clear()
                tick_state["peak"] = max(tick_state["peak"],
                                         sched.concurrency())
                sp.set_metadata(admitted=len(admitted),
                                blocks_claimed=claimed)

            # ---- chunked prefill: one chunk per prefilling slot per
            # tick, interleaved with the decode burst below; the policy
            # may cap chunk launches per tick while slots are decoding
            # (the slo policy's prefill/decode interleave budget) so
            # long-prompt admissions can't starve decode ticks
            prefill_slots = list(sched.prefilling.values())
            # sched.slots holds *started* (decoding) slots only —
            # prefilling slots live in the disjoint sched.prefilling
            # dict — so this is the decoding count the policy contract
            # wants: unlimited chunks while nothing is decoding
            n_decoding = len(sched.slots)
            budget = sched.policy.prefill_budget(n_decoding)
            if budget is not None:
                prefill_slots = prefill_slots[:budget]
            for slot in prefill_slots:
                pool, tok = self._prefill_chunk(pool, slot, tables, state)
                chunks += 1
                if tok is not None:
                    prefills += 1
                    sched.started(slot, tok, clock())
                    prefix.register(slot.request.prefix_id,
                                    slot.request.prompt,
                                    tables[slot.index])
                    release_if_finished(slot)

            # ---- copy-on-write guard: a decode write may never land in
            # a block another sequence can still read. The private copy
            # comes out of the slot's admission-time reserve, never a
            # fresh alloc — a full arena here must not raise OutOfBlocks
            active = sched.active()
            with M.span("engine.cow_guard") as sp:
                copies = 0
                for s in active:
                    j = s.length // bs
                    old = int(tables[s.index][j])
                    reserve = slot_reserve.get(s.index)
                    pool = alloc.ensure_writable(
                        tables[s.index], j, pool, reserve=reserve)
                    new = int(tables[s.index][j])
                    if new != old:
                        # the ownership list must track the swap: the
                        # shared block's ref was dropped by
                        # ensure_writable; the private copy is released
                        # at finish instead
                        row = slot_blocks[s.index]
                        row[row.index(old)] = new
                        slot_reserve.pop(s.index, None)
                        copies += 1
                    elif reserve is not None:
                        # first guarded decode tick and no copy was
                        # needed: every shared block sits strictly below
                        # the write frontier and the frontier block is
                        # now exclusively ours, so COW can never fire for
                        # this slot again — return the reserve instead of
                        # taxing the arena for the slot's whole lifetime
                        alloc.release([reserve])
                        slot_reserve.pop(s.index, None)

                # the decode step runs over *every* slot row; slots still
                # mid-chunked-prefill must not have their real blocks
                # stomped by the inactive-row write at position 0, so
                # their table rows are masked to the scratch block for
                # the burst
                decode_tables = tables
                if sched.prefilling:
                    decode_tables = tables.copy()
                    decode_tables[list(sched.prefilling)] = alloc.scratch
                sp.set_metadata(copies=copies)
            self.metrics.gauge("tick.free_blocks", alloc.n_free)
            # KV blocks reserved (not free) against blocks holding a
            # written position, at the burst's first tick; the arena
            # does not change inside a burst
            reserved = alloc.n_blocks - alloc.n_free
            held = prefix.blocks()
            for s in active:
                held.update(tables[s.index, :s.length // bs + 1].tolist())
            for s in sched.prefilling.values():
                held.update(tables[s.index, :-(-s.prefilled // bs)].tolist())
            lengths = [s.length for s in active]
            ticks0 = tick_state["ticks"]
            new_pool = self._decode_burst(sched, pool, state, tick_state,
                                          max_ticks, max_burst,
                                          tables=decode_tables)
            if new_pool is None:
                with M.span("engine.wait"):
                    feed.wait(sched, clock)  # future arrivals / live inbox
                continue
            pool = new_pool
            ticks = tick_state["ticks"] - ticks0
            used = block_ticks_used(len(held), lengths, bs, ticks)
            m = self.metrics
            m.count("kv.block_ticks_reserved", ticks * reserved)
            m.count("kv.block_ticks_used", used[0])
            m.gauge("kv.blocks_reserved", reserved)
            m.gauge("kv.blocks_used", used[1])
            for s in active:
                release_if_finished(s)

        prefix.drop_all()
        return self._stats(sched, tick_state, clock(), prefills, chunks)
