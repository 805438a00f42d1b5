"""Continuous (in-flight) batching over one shared ``SpecPVEngine``.

Slot-based scheduling: the engine's batch rows are B independent slots.
A request is admitted into any free slot as soon as one opens (chunked
batch-1 prefill scattered into the slot row), runs the SpecPV mode
automaton (Full -> Refresh -> Partial* -> Refresh) *per slot*, and is
evicted the moment it finishes, cancels, or misses its deadline — the
next waiting request takes the slot immediately, so divergent request
lengths never idle the batch the way wave draining does.

Each tick runs **one fused masked engine step** for all decoding slots
regardless of how their automata diverge: the per-slot modes ride into
the jitted step as a ``[B] int8`` vector (``SpecPVEngine.step_fused``),
so a tick whose slots want three different modes costs one dispatch
instead of three batch-wide masked steps.  ``fused=False`` keeps the
grouped path — one masked step per distinct mode per tick — for A/B
(``benchmarks/bench_serving.py --fused``).  Rows are computationally
independent either way, so every request's output is token-identical to
running it alone through ``SpecPVEngine.generate`` (greedy).  Admission
order is priority desc, then earliest deadline, then arrival.

Sampling rides *per request* on the same fused tick: admission threads
``Request.temperature`` / ``Request.seed`` / ``Request.draft`` into the
slot's prefill, which seeds a private per-slot PRNG stream in
``EngineState.keys`` and records the row's temperature and draft shape.
Greedy (temperature 0) rows take the argmax path bit-identically to a
sampling-free engine; sampled rows go through speculative-sampling
acceptance (``core/sampling.py``), which is lossless w.r.t. the
verifier's distribution.  Because the stream derives only from the
request's seed, a fixed (prompt, seed, temperature) reproduces the same
token stream regardless of batch composition or admission order.

With a paged engine (``SpecPVEngine(paged=True)``) admission is
additionally gated on free *pages*: a request is only admitted when the
shared block pools (trunk + draft) can hold its prompt + generation
budget, so short requests stop paying for max_len-sized rows and the
pool can be sized well below batch x max_len.  A request that does not
fit right now stays queued (``stats["page_stalls"]``) while smaller
waiters may proceed.

Admission accounting is *sharing-aware*: with the engine's prefix cache
on, ``pages_needed_shared`` subtracts the leading prompt blocks already
resident (they attach by refcounted page-table reference, skipping their
prefill entirely), under pool pressure idle cached prefixes are evicted
LRU before a request is stalled, and freeing a slot only reclaims pages
whose refcount drops to zero — pages still shared with another slot or
pinned by the prefix cache stay resident.

**Chunked-prefill interleaving** (``prefill_budget``): by default an
admission runs its *whole* prompt prefill inside ``_admit`` before the
tick's decode steps, so every in-flight request's inter-token latency
spikes by the full prefill time of each new long prompt.  With
``prefill_budget=N`` admission only *opens* a resumable prefill cursor
(``SpecPVEngine.prefill_begin_slot``; the request enters the
``PREFILLING`` phase) and each tick advances the open cursors — oldest
admission first — by whole chunks until ~N prompt tokens have run
(``_pump_prefill``), interleaved with the masked decode steps of the
DECODING slots.  Chunk boundaries stay absolute, so interleaved outputs
are token-identical to blocking ones; a tick processes at most
``max(prefill_budget, prefill_chunk)`` prefill tokens (one chunk always
runs when any cursor is open, so prefill can never starve), which bounds
the decode-tick jitter admission can inject.  Mid-prefill requests
honour cancellation and deadlines like any other slot: eviction drops
the cursor and releases the slot's page references, while prompt blocks
already registered in the prefix cache stay cached for future requests.

The lifecycle, admission/eviction rules and config knobs are documented
in docs/serving.md, whose symbol references CI checks against this file
(tools/check_docs.py).
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.engine import (MODE_FULL, MODE_NAMES, MODE_PARTIAL,
                               MODE_REFRESH, PrefillCursor, SpecPVEngine)
from repro.serving.request import Request, RequestOutput, RequestPhase
from repro.utils.spans import add as add_span, span


def trim_output(tokens: List[int], max_new: int, eos_id: int) -> np.ndarray:
    """Clip a generated-token list to the request contract: at most
    ``max_new`` tokens, truncated just after the first EOS."""
    row = np.asarray(tokens[:max_new], np.int64)
    if eos_id >= 0 and (row == eos_id).any():
        row = row[: int(np.argmax(row == eos_id)) + 1]
    return row


@dataclass
class _Slot:
    req: Request
    admit_s: float
    seq: int = 0                    # admission order (prefill FIFO)
    cursor: Optional[PrefillCursor] = None  # open resumable prefill
    tokens: List[int] = field(default_factory=list)
    accepts: List[int] = field(default_factory=list)
    steps: int = 0
    eos_at: Optional[int] = None    # index of the first EOS, tracked as
                                    # tokens append (done_reason is O(1))

    def append(self, toks: List[int]) -> None:
        if self.req.eos_id >= 0 and self.eos_at is None:
            for j, t in enumerate(toks):
                if t == self.req.eos_id:
                    self.eos_at = len(self.tokens) + j
                    break
        self.tokens.extend(toks)

    def done_reason(self) -> Optional[str]:
        if self.eos_at is not None and self.eos_at < self.req.max_new_tokens:
            return "stop"
        if len(self.tokens) >= self.req.max_new_tokens:
            return "length"
        return None


class ContinuousScheduler:
    """Slot scheduler over one shared ``SpecPVEngine`` (see module
    docstring and docs/serving.md for the lifecycle and invariants).

    ``prefill_budget=None`` (default) admits blocking: a request's whole
    prompt prefills inside its admission tick.  ``prefill_budget=N``
    interleaves: each tick advances open prefill cursors by whole chunks
    up to ~N prompt tokens before running the decode steps (at most
    ``max(N, prefill_chunk)`` tokens per tick; at least one chunk runs
    whenever a cursor is open).  ``record_steps`` appends
    ``(clock(), request_id, n_tokens)`` to ``step_log`` for every slot
    that decodes in a tick — the per-request inter-step gap trace the
    jitter benchmark (``bench_serving.py --interleave``) is built on.

    ``fused=True`` (default) decodes every tick with a single fused
    multi-mode dispatch; ``fused=False`` runs the grouped per-mode loop
    (one masked step per distinct mode) for A/B.  Stats distinguish the
    two costs explicitly: ``stats["steps"]`` counts *jitted dispatches*,
    ``stats["mode_rows_<mode>"]`` counts per-mode stepped rows (the
    logical per-mode work), and ``stats["ticks_modes_<k>"]`` histograms
    decode ticks by their number of distinct modes.

    ``fused_prefill=True`` (default) spends the prefill budget as a
    per-round *row set*: every open cursor that fits advances one chunk
    in a single fused dispatch (``SpecPVEngine.prefill_step_fused``) —
    N concurrent admissions cost one kernel launch per round instead of
    N.  ``fused_prefill=False`` keeps the serial oldest-first pump for
    A/B (``bench_serving.py --prefill-batch``).  Outputs are
    token-identical either way (absolute chunk boundaries, zero-pad-only
    packing); ``stats["prefill_dispatches"]`` counts the launches."""

    def __init__(self, engine: SpecPVEngine, *, prefill_chunk: int = 256,
                 prefill_budget: Optional[int] = None,
                 record_steps: bool = False,
                 fused: bool = True,
                 fused_prefill: bool = True,
                 clock: Callable[[], float] = time.time):
        assert engine.is_attn, \
            "continuous batching drives the per-slot SpecPV automaton " \
            "(attention archs); state archs use the wave scheduler"
        assert engine.temperature == 0.0, \
            "build the engine greedy; per-request sampling rides on " \
            "Request.temperature/seed (per-slot PRNG streams)"
        assert prefill_budget is None or prefill_budget > 0, \
            "prefill_budget must be positive (None = blocking prefill)"
        self.engine = engine
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget
        self.record_steps = record_steps
        self.fused = fused
        self.fused_prefill = fused_prefill
        self.clock = clock
        self.st = engine.empty_state()
        self.slots: List[Optional[_Slot]] = [None] * engine.batch
        self._dirty: set = set()        # evicted, not yet reset/refilled
        self._seq = 0                   # admission counter (prefill FIFO)
        self.waiting: List[Request] = []
        self.outputs: Dict[str, RequestOutput] = {}
        self.done_order: List[RequestOutput] = []
        self.trace: List[tuple] = []        # (event, request_id, slot)
        self.step_log: List[tuple] = []     # (t, request_id, n_tokens)
        self.stats = defaultdict(float)
        # the engine's spans land in this same mapping
        # (``repro.utils.spans``; docs/serving.md#observability)
        engine.stats = self.stats

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.waiting.append(req)

    def cancel(self, request_id: str) -> bool:
        """Mark a waiting or in-flight request cancelled (takes effect at
        the next tick).  Returns False for unknown/finished requests."""
        for r in self.waiting:
            if r.request_id == request_id:
                r.cancel()
                return True
        for s in self.slots:
            if s is not None and s.req.request_id == request_id:
                s.req.cancel()
                return True
        return False

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def has_work(self) -> bool:
        return bool(self.waiting) or self.num_active > 0

    # ------------------------------------------------------------------
    def _emit(self, req: Request, slot: int, tokens: List[int],
              finished: bool, reason: str, *, accepts=(), steps=0) -> None:
        out = RequestOutput(
            request_id=req.request_id,
            tokens=trim_output(tokens, req.max_new_tokens, req.eos_id),
            prompt_len=len(req.prompt), finished=finished, slot=slot,
            finish_reason=reason,
            # clamp: a request cancelled/expired before its (future)
            # arrival offset would otherwise report a negative latency
            latency_s=max(0.0, self.clock() - req.arrival_s),
            mean_accept=float(np.mean(accepts)) if len(accepts) else 0.0,
            tokens_per_step=(len(tokens) / steps if steps else 0.0))
        req.phase = RequestPhase.FINISHED
        self.outputs[req.request_id] = out
        self.done_order.append(out)
        self.stats["tokens"] += len(out.tokens)
        self.trace.append(("finish:" + reason, req.request_id, slot))

    def _evict(self, i: int, reason: str) -> None:
        s = self.slots[i]
        self._emit(s.req, i, s.tokens, finished=(reason in ("stop", "length")),
                   reason=reason, accepts=s.accepts, steps=s.steps)
        self.slots[i] = None
        # pages go back to the free list immediately so same-tick
        # admission sees them; the device-row reset stays deferred
        self.engine.release_slot_pages(i)
        # state reset is deferred to after admission: a same-tick refill
        # overwrites the whole row during prefill-into-slot anyway
        self._dirty.add(i)

    # ------------------------------------------------------------------
    def _admissible(self, now: float) -> List[Request]:
        ready = [r for r in self.waiting if r.arrival_s <= now]
        return sorted(ready, key=Request.admission_key)

    def _admit(self) -> None:
        now = self.clock()
        # drop cancelled / expired waiters first
        for r in list(self.waiting):
            if r.cancelled:
                self.waiting.remove(r)
                self._emit(r, -1, [], finished=False, reason="cancelled")
            elif r.deadline_s is not None and r.deadline_s < now:
                self.waiting.remove(r)
                self._emit(r, -1, [], finished=False, reason="deadline")
        free = [i for i, s in enumerate(self.slots) if s is None]
        for req in self._admissible(now):
            if not free:
                break
            need = len(req.prompt) + req.max_new_tokens + self.engine.pmax
            need_pages = self.engine.pages_needed(len(req.prompt),
                                                  req.max_new_tokens)
            if (need > self.engine.max_len
                    or need_pages > self.engine.page_capacity()):
                self.waiting.remove(req)
                self._emit(req, -1, [], finished=False, reason="rejected")
                continue
            shards = getattr(self.engine, "data_shards", 1)
            pick: Optional[int] = None
            if self.engine.paged:
                # sharing-aware gate: only the *fresh* pages beyond the
                # request's prefix-cache hits must be free; under
                # pressure, idle cached prefixes are LRU-evicted first.
                # The gate only COUNTS (touch=False): the single LRU
                # re-stamp happens inside the actual admission's prefill
                # attach — a request that stalls here must not re-stamp
                # its chain every tick (skewing eviction order) nor
                # inflate the hit counters with probes.  A same-tick
                # reclaim can therefore evict the counted chain, but the
                # post-reclaim re-count below re-bills before the gate
                # decides, and nothing else runs between a passed gate
                # and the admission's own match.
                # Tiered engines additionally reserve promotion headroom
                # (tier_admit_margin): admission must never pack the
                # pool so tight that a live slot's demoted pages can no
                # longer be seated for its next refresh.
                # Data-sharded engines pick the slot (hence the per-host
                # page pool shard) with the most free pages whose shard
                # passes the gate — the prefix match and the free-page
                # bill are both shard-local, so no host is ever billed
                # for pages another host holds.
                margin = self.engine.tier_admit_margin(len(req.prompt))
                if shards > 1:
                    cands = sorted(
                        {self.engine.shard_of_slot(i) for i in free},
                        key=lambda s: -self.engine.free_pages(s))
                else:
                    cands = [None]
                for sh in cands:
                    need_fresh = self.engine.pages_needed_shared(
                        req.prompt, req.max_new_tokens, touch=False,
                        shard=sh, temperature=req.temperature)
                    short = (need_fresh + margin
                             - self.engine.free_pages(sh))
                    if short > 0:
                        self.stats["prefix_evictions"] += \
                            self.engine.reclaim_pages(short)
                        # eviction may have shortened this request's own
                        # matched chain (LRU has no pin) — re-count so
                        # the gate never passes on a stale, smaller bill
                        need_fresh = self.engine.pages_needed_shared(
                            req.prompt, req.max_new_tokens, touch=False,
                            shard=sh, temperature=req.temperature)
                    if (need_fresh + margin
                            <= self.engine.free_pages(sh)):
                        pick = (free[0] if sh is None else next(
                            i for i in free
                            if self.engine.shard_of_slot(i) == sh))
                        break
                if pick is None:
                    # the request stays queued; smaller waiters may fit
                    self.stats["page_stalls"] += 1
                    continue
            if pick is None:
                i = free.pop(0)
            else:
                i = pick
                free.remove(i)
            self.waiting.remove(req)
            req.phase = RequestPhase.PREFILLING
            slot = _Slot(req=req, admit_s=now, seq=self._seq)
            self._seq += 1
            knobs = dict(chunk=self.prefill_chunk,
                         max_new_tokens=req.max_new_tokens,
                         temperature=req.temperature, seed=req.seed,
                         draft=req.draft, request_id=req.request_id)
            with span(self.stats, "sched.admit.request",
                      request_id=req.request_id):
                if self.prefill_budget is None:
                    # blocking admission: the whole prompt prefills now
                    self.st, first = self.engine.prefill_into_slot(
                        self.st, i, req.prompt, **knobs)
                    req.phase = RequestPhase.DECODING
                    slot.append([first])
                else:
                    # interleaved admission: open a resumable cursor;
                    # chunks run inside _pump_prefill under the budget
                    self.st, slot.cursor = self.engine.prefill_begin_slot(
                        self.st, i, req.prompt, **knobs)
            self._dirty.discard(i)
            self.slots[i] = slot
            self.stats["admissions"] += 1
            self.trace.append(("admit", req.request_id, i))
        # slots that stayed free get their rows zeroed once
        for i in sorted(self._dirty):
            self.st = self.engine.reset_slot(self.st, i)
        self._dirty.clear()

    def _finalize_prefill(self, i: int) -> None:
        """Commit an exhausted cursor: scatter the sub-state into the
        slot row, append the first token, enter DECODING — eligible for
        a decode step in this same tick."""
        s = self.slots[i]
        self.st, first = self.engine.prefill_finalize_slot(self.st, s.cursor)
        s.cursor = None
        s.req.phase = RequestPhase.DECODING
        s.append([first])
        self.trace.append(("prefill_done", s.req.request_id, i))

    def _pump_prefill(self) -> int:
        """Spend the per-tick prefill budget on the open cursors.

        Fused (default): each round selects the oldest-first *row set*
        whose next chunks fit the remaining budget (the first row always
        runs, so a budget below the chunk size still progresses) and
        advances the whole set in ONE fused dispatch
        (``prefill_step_fused``); cursors carrying per-request ``extra``
        conditioning cannot batch and step serially within their round.
        Serial (``fused_prefill=False``): the classic pump — one cursor
        at a time, oldest admission first, one dispatch per chunk.

        Both spend at most ``max(prefill_budget, prefill_chunk)`` tokens
        per tick (fused: per selected row) and produce token-identical
        outputs; cursors that exhaust their prompt are finalised
        (incl. cursors born exhausted: a whole-prompt tail-entry hit
        opens with zero chunks to run).  Returns tokens processed."""
        if not self.fused_prefill:
            return self._pump_prefill_serial()
        spent, d0 = 0, self.engine.prefill_dispatches
        while True:
            order = sorted((s.seq, i) for i, s in enumerate(self.slots)
                           if s is not None and s.cursor is not None)
            for _, i in order:
                if self.slots[i].cursor.done:
                    self._finalize_prefill(i)
            open_rows = [i for _, i in order
                         if self.slots[i].cursor is not None]
            if not open_rows or (spent and spent >= self.prefill_budget):
                break
            # oldest-first row set under the remaining budget; the first
            # row is unconditional only while nothing ran this tick
            batch, planned = [], spent
            for i in open_rows:
                nxt = self.slots[i].cursor.next_tokens
                if (spent or batch) and planned + nxt > self.prefill_budget:
                    break
                batch.append(i)
                planned += nxt
            if not batch:
                break                       # budget exhausted mid-tick
            fused_rows = [i for i in batch
                          if self.slots[i].cursor.extra is None]
            if fused_rows:
                self.st, n = self.engine.prefill_step_fused(
                    self.st, [self.slots[i].cursor for i in fused_rows])
                spent += n
            for i in batch:                 # `extra` rows: serial fallback
                if i not in fused_rows:
                    self.st, n = self.engine.prefill_step_into_slot(
                        self.st, self.slots[i].cursor)
                    spent += n
        if spent:
            self.stats["prefill_tokens"] += spent
            self.stats["prefill_dispatches"] += \
                self.engine.prefill_dispatches - d0
        return spent

    def _pump_prefill_serial(self) -> int:
        """A/B reference pump: advance open prefill cursors, oldest
        admission first, by whole chunks until the per-tick budget is
        spent (the first chunk always runs).  One jitted dispatch per
        chunk per cursor."""
        spent, d0 = 0, self.engine.prefill_dispatches
        order = sorted((s.seq, i) for i, s in enumerate(self.slots)
                       if s is not None and s.cursor is not None)
        for _, i in order:
            s = self.slots[i]
            while s.cursor is not None:
                if not s.cursor.done:
                    if spent and spent + s.cursor.next_tokens > \
                            self.prefill_budget:
                        break
                    self.st, n = self.engine.prefill_step_into_slot(
                        self.st, s.cursor)
                    spent += n
                if s.cursor.done:
                    self._finalize_prefill(i)
            if spent and spent >= self.prefill_budget:
                break
        if spent:
            self.stats["prefill_tokens"] += spent
            self.stats["prefill_dispatches"] += \
                self.engine.prefill_dispatches - d0
        return spent

    # ------------------------------------------------------------------
    def tick(self) -> bool:
        """One scheduler round: evict, admit, pump prefill chunks (when
        interleaving), step the decoding slots.  Returns True when any
        work ran — a decode step or prefill progress (False = idle).
        Each phase is a span (``sched.*``, docs/serving.md#observability)
        inside the tick's own ``sched.tick``."""
        with span(self.stats, "sched.tick"):
            return self._tick()

    def _tick(self) -> bool:
        # evictions: cancellation first, then natural completion (a slot
        # can satisfy its stop condition during the previous tick's step),
        # then deadline misses — an in-flight request past its deadline_s
        # is evicted with its partial tokens, same as an expired waiter.
        # All three apply to PREFILLING slots too: eviction drops the
        # cursor (pages released via _evict; registered prefix blocks
        # stay cached) and the request reports whatever it has (nothing).
        now = self.clock()
        with span(self.stats, "sched.evict"):
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                if s.req.cancelled:
                    self._evict(i, "cancelled")
                elif s.done_reason():
                    self._evict(i, s.done_reason())
                elif (s.req.deadline_s is not None
                      and s.req.deadline_s < now):
                    self._evict(i, "deadline")
        with span(self.stats, "sched.admit"):
            self._admit()
        prefilled = 0
        if self.prefill_budget:
            with span(self.stats, "sched.prefill"):
                prefilled = self._pump_prefill()

        # decode: slots mid-prefill have no automaton state yet and sit
        # this phase out (their device rows are neutral — masked steps
        # treat them exactly like empty slots)
        active = np.array([s is not None and s.cursor is None
                           for s in self.slots], bool)
        self.stats["peak_active"] = max(self.stats["peak_active"],
                                        float(np.sum(active)))
        if not active.any():
            return prefilled > 0
        with span(self.stats, "sched.modes"):
            modes = self.engine.modes_for_rows(self.st, active)
            # tiered engines: refresh rows whose promotion cannot seat
            # this tick sit it out (pages return as other slots
            # re-demote).  Only force the all-deferred fallback when
            # nothing else progressed: a pumping prefill cursor holds its
            # full page bill until its first refresh, and its completion
            # is what unblocks the pool.
            active, deferred = self.engine.tier_ready_rows(
                active, modes, force=(prefilled == 0))
        if deferred:
            self.stats["tier_defers"] += deferred
        if not active.any():
            return prefilled > 0
        distinct = sorted({int(m) for m in modes[active]})
        self.stats[f"ticks_modes_{len(distinct)}"] += 1
        for mid in distinct:
            self.stats["mode_rows_" + MODE_NAMES[mid]] += int(
                np.sum(active & (modes == mid)))
        with span(self.stats, "sched.decode") as dec:
            if self.fused:
                # the whole mode mix in ONE jitted dispatch
                self.st, so = self.engine.step_fused(self.st, active, modes)
                self.stats["steps"] += 1
                self._harvest(so, active)
            else:
                # grouped A/B path: one masked dispatch per distinct mode
                for mid in distinct:
                    mask = active & (modes == mid)
                    self.st, so = self.engine.step_rows(
                        self.st, MODE_NAMES[mid], mask)
                    self.stats["steps"] += 1
                    self._harvest(so, mask)
        # decode wall time by tick class too (the step fetched its
        # tokens, so the dispatch has drained)
        add_span(self.stats, "sched.decode." + self._tick_class(modes, active),
                 dec.seconds)
        return True

    @staticmethod
    def _tick_class(modes: np.ndarray, active: np.ndarray) -> str:
        """Classify a decode tick for the wall-time breakdown: the
        refresh cost dominates any tick containing one, so "refresh"
        wins outright; an all-partial tick is the steady-state cheap
        case; everything else is full-only or a full+partial mix."""
        m = modes[active]
        if np.any(m == MODE_REFRESH):
            return "refresh"
        if np.all(m == MODE_PARTIAL):
            return "partial"
        return "full" if np.all(m == MODE_FULL) else "mixed"

    def _harvest(self, so, mask: np.ndarray) -> None:
        """Collect one step's tokens into the stepped slots (+ the
        step-gap log when ``record_steps``)."""
        t_step = self.clock() if self.record_steps else 0.0
        for i in np.nonzero(mask)[0]:
            s = self.slots[i]
            s.append([int(x) for x in so.tokens[i, : so.counts[i]]])
            s.accepts.append(int(so.accept_len[i]))
            s.steps += 1
            if self.record_steps:
                self.step_log.append((t_step, s.req.request_id,
                                      int(so.counts[i])))

    def run(self) -> List[RequestOutput]:
        """Drive ticks until the queue and all slots drain.  Returns this
        call's outputs in completion order.

        Assumes ``clock`` advances with wall time (it gates admission and
        stamps latency); a frozen/simulated clock must drive ``tick()``
        directly instead of using ``run``, which real-sleeps while waiting
        for future arrivals."""
        t0 = self.clock()
        start = len(self.done_order)
        while self.has_work():
            progressed = self.tick()
            if not progressed and self.waiting:
                # all slots idle; next request hasn't arrived yet
                delay = min(r.arrival_s for r in self.waiting) - self.clock()
                if delay > 0:
                    time.sleep(min(delay, 0.02))
        self.stats["wall_s"] += self.clock() - t0
        return self.done_order[start:]
