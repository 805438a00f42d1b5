"""Batched SpecPV serving engine with two schedulers.

``ServingConfig.scheduler`` selects how batch slots are filled:

* ``"continuous"`` (default) — in-flight batching
  (``repro.serving.scheduler.ContinuousScheduler``): the engine's batch
  rows are independent slots; a request is admitted the moment a slot
  frees up (chunked batch-1 prefill scattered into the slot row), the
  SpecPV mode automaton (Full -> Refresh -> Partial* -> Refresh) runs
  *per slot*, and eviction is per-slot — mixed request lengths never
  drain-idle the batch.  Greedy outputs are token-identical to running
  each request alone through ``SpecPVEngine.generate``.  Supports
  priorities, deadlines and cancellation (see ``serving.request``).
  Per-request sampling (``Request.temperature`` / ``seed`` / ``draft``)
  rides on the same fused tick via per-slot PRNG streams — the engine
  itself is built greedy; sampled rows are lossless w.r.t. the
  verifier's distribution and reproducible from the request seed alone
  (docs/serving.md).

* ``"wave"`` — the original lock-step scheduler, kept for A/B
  comparison (``benchmarks/bench_serving.py``): pending requests are
  bucketed by prompt length, executed as fixed-size waves through one
  shared ``SpecPVEngine``, and a whole wave drains before the next is
  admitted.  Slots idle whenever request lengths diverge, which is
  exactly what continuous batching removes.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig, SpecPVConfig, DraftConfig
from repro.core.engine import SpecPVEngine
from repro.serving.request import Request, RequestOutput
from repro.serving.scheduler import ContinuousScheduler, trim_output


@dataclass
class ServingConfig:
    batch: int = 4
    max_len: int = 4096
    prefill_chunk: int = 256
    # chunked-prefill interleaving (continuous scheduler only).  None:
    # an admission runs its whole prompt prefill before the tick's
    # decode steps (blocking).  N: each tick runs at most
    # ~max(N, prefill_chunk) prompt tokens of the open prefill cursors,
    # interleaved with decode — bounding the decode-tick jitter a long
    # admission injects while outputs stay token-identical
    # (see docs/serving.md).
    prefill_budget: Optional[int] = None
    # fused multi-mode decode (continuous scheduler only): one jitted
    # masked step per tick regardless of how the slots' SpecPV automata
    # diverge (the per-row mode vector is an operand of the step).
    # False keeps the grouped per-mode loop — one dispatch per distinct
    # mode per tick — as the A/B baseline
    # (``benchmarks/bench_serving.py --fused``).
    fused_step: bool = True
    # fused multi-row prefill (continuous scheduler only): each tick's
    # prefill budget is spent as a *row set* — every open admission's
    # next chunk runs in one ragged fused dispatch instead of one
    # dispatch per cursor.  False keeps the serial oldest-first pump as
    # the A/B baseline (``benchmarks/bench_serving.py --prefill-batch``).
    # Token outputs are bit-identical either way.
    fused_prefill: bool = True
    partial_verification: bool = True
    pad_id: int = 0
    # "continuous" | "wave".  Continuous batching drives the per-slot
    # attention automaton; state archs (ssm/hybrid) run chain
    # verification and automatically fall back to the wave path.
    scheduler: str = "continuous"
    # paged full-KV cache (continuous scheduler only): back the engine's
    # batch rows (trunk AND draft caches) with shared block pools +
    # per-slot page tables and gate admission on free pages.
    # num_pages=None sizes the pools at contiguous parity
    # (batch * max_len/block + 1); smaller pools trade concurrency for
    # memory.  The wave path always runs contiguous.
    paged_kv: bool = False
    num_pages: Optional[int] = None
    # draft-pool page count (paged only; default: num_pages).  Tiered
    # deployments shrink the trunk pool but keep a full-size draft pool
    # — draft pages are ~1/L the bytes and are read every step, so the
    # draft cache never tiers.
    num_draft_pages: Optional[int] = None
    # tiered KV residency (paged only): after each refresh a slot's
    # cold committed blocks are demoted to host RAM as int8 (raw fp
    # when tier_lossless=True — bit-identical round-trip) and
    # prefetched back one mode-transition ahead of the next refresh,
    # so the trunk pool sizes to the *hot* working set
    # (benchmarks/bench_serving.py --tiered).
    tiered_kv: bool = False
    tier_lossless: bool = False
    # host-side page codec for demoted blocks (paged + tiered only):
    # "int8" (absmax per-token symmetric) or "fp8" (e4m3 cast with a
    # per-token absmax/448 scale — same byte footprint, no integer
    # rounding grid).  Ignored when tier_lossless=True.
    tier_codec: str = "int8"
    # zero-copy partial verification (paged only): the partial KV is a
    # page-table-routed view over the trunk pool — a refresh writes
    # O(budget) selected-block indices and pins the selected pages
    # instead of copying their bytes into a dense per-slot buffer.
    # Greedy outputs are token-identical to the gathered baseline
    # (benchmarks/bench_serving.py --zero-copy).
    zero_copy_partial: bool = False
    # copy-on-write prompt-prefix sharing (paged only): requests whose
    # prompts share block-aligned leading tokens attach the cached pages
    # by reference — one physical copy, zero prefill FLOPs for the
    # shared prefix — and admission subtracts the hits from the page
    # bill.  Off: every request pays for its whole prompt (A/B baseline).
    prefix_cache: bool = True
    # mesh-parallel serving: a (data, model) device-mesh shape, e.g.
    # (8, 1).  The continuous engine shards its batch rows, page pools
    # and page tables over the ``data`` axis (per-host page pools — no
    # host materializes the whole cache or batch) and trunk weights
    # over ``model`` (see docs/architecture.md#mesh--sharding).  None
    # runs unsharded; a shape the devices cannot fill raises.
    mesh_shape: Optional[tuple] = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, spec: SpecPVConfig,
                 dcfg: DraftConfig, params, draft_params,
                 scfg: Optional[ServingConfig] = None):
        self.cfg = cfg
        self.spec = spec
        self.dcfg = dcfg
        self.scfg = scfg or ServingConfig()
        self.params = params
        self.dparams = draft_params
        self.queue: List[Request] = []
        self.outputs: Dict[str, RequestOutput] = {}
        self._engines: Dict[tuple, SpecPVEngine] = {}
        self._continuous: Optional[ContinuousScheduler] = None
        self._wave_id = 0
        self.stats = defaultdict(float)

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued or (continuous scheduler) in-flight request.
        The wave path honours cancellation at wave boundaries only — a
        request already inside a running wave completes (lock-step
        generation cannot evict mid-wave)."""
        for r in self.queue:
            if r.request_id == request_id:
                r.cancel()
                return True
        if self._continuous is not None:
            return self._continuous.cancel(request_id)
        return False

    def _mesh(self):
        """The serving mesh per ``ServingConfig.mesh_shape`` (None when
        unsharded).  Raises when the devices cannot fill the shape: a
        sharded deployment never silently runs on one device."""
        shape = self.scfg.mesh_shape
        if shape is None:
            return None
        import jax
        import math
        from repro.launch.mesh import make_mesh
        if math.prod(shape) > jax.device_count():
            raise ValueError(
                f"mesh_shape {tuple(shape)} needs {math.prod(shape)} "
                f"devices; {jax.device_count()} available")
        return make_mesh(shape, ("data", "model"),
                         devices=jax.devices()[:math.prod(shape)])

    def _engine_for(self, batch: int, *, paged: bool = False) -> SpecPVEngine:
        key = (batch, paged)
        if key not in self._engines:
            self._engines[key] = SpecPVEngine(
                self.cfg, self.spec, self.dcfg, self.params, self.dparams,
                batch=batch, max_len=self.scfg.max_len,
                partial_verification=self.scfg.partial_verification,
                paged=paged, num_pages=self.scfg.num_pages,
                num_draft_pages=self.scfg.num_draft_pages,
                prefix_cache=self.scfg.prefix_cache,
                tiered=paged and self.scfg.tiered_kv,
                tier_lossless=self.scfg.tier_lossless,
                tier_codec=self.scfg.tier_codec,
                zero_copy=paged and self.scfg.zero_copy_partial,
                mesh=self._mesh())
        return self._engines[key]

    def page_stats(self) -> Dict[str, int]:
        """Resident-page accounting of the continuous engine ({} when not
        paged)."""
        key = (self.scfg.batch, True)
        return self._engines[key].page_stats() if key in self._engines else {}

    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache hit/reuse accounting of the continuous engine
        ({} when not paged or sharing is off)."""
        key = (self.scfg.batch, True)
        return (self._engines[key].prefix_stats()
                if key in self._engines else {})

    def reset_page_high_water(self) -> None:
        """Zero the resident-page high-water marks (e.g. after a warmup
        run, so they reflect only the timed region)."""
        key = (self.scfg.batch, True)
        if key in self._engines:
            self._engines[key].reset_high_water()

    def reset_warm(self) -> None:
        """Forget everything a warmup run left behind: outputs/stats,
        the continuous scheduler (the next ``run()`` boots a fresh one,
        resetting the allocators and clearing the prefix cache), and the
        page / prefix counters.  Jitted step functions stay compiled —
        that is the point of warming up."""
        self.stats.clear()
        self.outputs.clear()
        self._continuous = None
        self.reset_page_high_water()
        key = (self.scfg.batch, True)
        if key in self._engines:
            self._engines[key].reset_prefix_stats()

    # ------------------------------------------------------------------
    # continuous (in-flight) scheduler
    # ------------------------------------------------------------------
    def _run_continuous(self) -> List[RequestOutput]:
        sched = self._continuous
        if sched is None:
            sched = ContinuousScheduler(
                self._engine_for(self.scfg.batch, paged=self.scfg.paged_kv),
                prefill_chunk=self.scfg.prefill_chunk,
                prefill_budget=self.scfg.prefill_budget,
                fused=self.scfg.fused_step,
                fused_prefill=self.scfg.fused_prefill)
            self._continuous = sched
        while self.queue:
            sched.submit(self.queue.pop(0))
        done = sched.run()
        self.outputs.update({o.request_id: o for o in done})
        # peak concurrency is a max, not a sum (tiered A/B headline)
        self.stats["peak_active"] = max(self.stats["peak_active"],
                                        sched.stats.pop("peak_active", 0.0))
        for k in list(sched.stats):
            if k in ("tokens", "wall_s", "steps", "admissions",
                     "page_stalls", "prefix_evictions", "prefill_tokens",
                     "prefill_dispatches", "tier_defers") \
                    or k.startswith(("mode_rows_", "ticks_modes_",
                                     "span_s.", "span_n.")):
                self.stats[k] += sched.stats.pop(k)
        # sharded engines: the headline residency number is the worst
        # single host, not the pool total (a max across hosts AND runs)
        ps = self.page_stats()
        if "peak_pages_per_host" in ps:
            self.stats["peak_pages_per_host"] = max(
                self.stats["peak_pages_per_host"],
                float(ps["peak_pages_per_host"]))
        return done

    # ------------------------------------------------------------------
    # wave scheduler (A/B baseline)
    # ------------------------------------------------------------------
    def _next_wave(self) -> Optional[List[Request]]:
        if not self.queue:
            return None
        buckets: Dict[int, List[Request]] = defaultdict(list)
        for r in self.queue:
            buckets[len(r.prompt)].append(r)
        # largest bucket first
        length = max(buckets, key=lambda k: len(buckets[k]))
        wave = buckets[length][: self.scfg.batch]
        for r in wave:
            self.queue.remove(r)
        # pad the wave to full batch by repeating the last request (its
        # output is discarded) so the jitted step shapes stay constant
        while len(wave) < self.scfg.batch:
            wave.append(wave[-1])
        return wave

    def run_one_wave(self) -> List[RequestOutput]:
        """Execute a single wave from the queue (benchmark driver hook:
        lets callers interleave arrivals between waves).  Returns that
        wave's outputs ([] when the queue is empty)."""
        done: List[RequestOutput] = []
        now = time.time()
        for r in list(self.queue):        # honour pre-wave cancellations
            if r.cancelled:
                self.queue.remove(r)
                out = RequestOutput(
                    request_id=r.request_id,
                    tokens=np.zeros((0,), np.int64),
                    prompt_len=len(r.prompt), finished=False,
                    finish_reason="cancelled",
                    latency_s=now - r.arrival_s)
                self.outputs[r.request_id] = out
                done.append(out)
        wave = self._next_wave()
        if wave is None:
            return done
        t0 = time.time()
        engine = self._engine_for(len(wave))
        prompts = np.stack([r.prompt for r in wave])
        max_new = max(r.max_new_tokens for r in wave)
        eos = wave[0].eos_id
        toks, stats = engine.generate(
            prompts, max_new, eos_id=eos,
            prefill_chunk=self.scfg.prefill_chunk)
        t_done = time.time()
        dt = t_done - t0
        seen = set()
        for i, r in enumerate(wave):
            if r.request_id in seen:
                continue
            seen.add(r.request_id)
            raw = toks[i]
            row = trim_output([int(x) for x in raw[raw >= 0]],
                              r.max_new_tokens, r.eos_id)
            reason = ("stop" if r.eos_id >= 0 and row.size
                      and row[-1] == r.eos_id else "length")
            out = RequestOutput(
                request_id=r.request_id, tokens=row,
                prompt_len=len(r.prompt), finished=True,
                wave_id=self._wave_id, finish_reason=reason,
                latency_s=t_done - r.arrival_s,
                mean_accept=stats["mean_accept"],
                tokens_per_step=stats["tokens_per_step"])
            self.outputs[r.request_id] = out
            done.append(out)
        self.stats["waves"] += 1
        self.stats["wall_s"] += dt
        self.stats["tokens"] += sum(len(o.tokens) for o in done)
        self._wave_id += 1
        return done

    def _run_wave(self) -> List[RequestOutput]:
        done: List[RequestOutput] = []
        while self.queue:
            done.extend(self.run_one_wave())
        return done

    # ------------------------------------------------------------------
    def run(self) -> List[RequestOutput]:
        """Drain the queue; returns outputs in completion order."""
        if self.scfg.scheduler == "continuous":
            if self.cfg.is_attention_arch:
                return self._run_continuous()
            return self._run_wave()        # state archs: lock-step only
        if self.scfg.scheduler == "wave":
            return self._run_wave()
        raise ValueError(f"unknown scheduler {self.scfg.scheduler!r}")

    def throughput_tok_s(self) -> float:
        return self.stats["tokens"] / max(self.stats["wall_s"], 1e-9)
