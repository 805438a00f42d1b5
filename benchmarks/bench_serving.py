"""Serving-scheduler A/B: continuous (in-flight) batching vs the wave
scheduler on a mixed-length Poisson workload.

Requests with mixed context lengths arrive as a Poisson process; both
schedulers serve the identical request set.  The wave scheduler buckets
by prompt length and drains whole waves (idling slots whenever lengths
diverge); the continuous scheduler admits into any free slot as soon as
one opens.  Reports throughput (tok/s) and p50/p95 request latency
(completion - arrival), and — unless --no-check — verifies every
continuous-scheduler output is token-identical to running that request
alone through ``SpecPVEngine.generate`` (the SpecPV losslessness
anchor).

``--paged`` backs the continuous scheduler with the paged full-KV cache
(shared block pool + per-slot page tables): the pool defaults to ~60% of
the contiguous batch x max_len reservation, admission is gated on free
pages, and the run reports the resident-page high-water mark — i.e. the
engine serves the same request set (still token-identical) while holding
less than batch rows' worth of max_len memory.  ``--num-pages`` overrides
the pool size (incl. the reserved null page).

``--prefix-share`` switches to a templated-prompt workload (Poisson
arrivals drawing from a small set of shared system prompts, each with a
unique user tail) and A/Bs the paged continuous scheduler with the
copy-on-write prefix cache on vs off: matched leading blocks attach by
refcounted page-table reference, so the run reports the prefix-cache hit
rate, pages saved by sharing, and prefill tokens skipped, alongside the
resident-page high-water mark of both runs (sharing holds one physical
copy of each hot prefix; the baseline re-stores it per request).

``--interleave`` A/Bs blocking admission against chunked-prefill
interleaving (``ServingConfig(prefill_budget=...)``) on a mixed-length
Poisson workload with some long prompts: blocking runs a newly admitted
prompt's whole prefill before the next decode tick, so every in-flight
request's inter-token gap spikes by the full prefill time; interleaving
caps each tick at ~``--prefill-budget`` prefill tokens.  Both runs serve
the identical request set with token-identical outputs (verified); the
report compares per-request decode-step gaps (p50/p95 and jitter =
p95 - p50, from the scheduler's ``step_log``) and request latency.

``--fused`` A/Bs grouped-per-mode vs fused decode ticks on a
mixed-length Poisson workload straddling the partial budget (so
in-flight slots routinely diverge into distinct SpecPV modes): grouped
scheduling runs one batch-wide masked step per distinct mode per tick,
the fused step (``ServingConfig(fused_step=True)``, the default) folds
the whole mode mix into a single jitted dispatch.  The run reports the
distinct-modes-per-tick histogram, jitted dispatches per decode tick,
per-mode stepped rows, and decode-step gap p50/p95, and verifies the
two schedules produce token-identical outputs.

``--prefill-batch`` A/Bs the serial vs fused prefill pump on a
long-prompt burst (``ServingConfig(fused_prefill=...)``): a burst of
long prompts opens several prefill cursors at once; the serial pump
advances them one chunk per jitted dispatch (N open cursors = N
launches per round), the fused pump packs every cursor that fits the
per-tick budget into ONE multi-row dispatch
(``SpecPVEngine.prefill_step_fused``).  Reports prefill dispatches per
prefill tick, admission-to-first-token p50/p95, and decode-step gap
p50/p95, and verifies the two pumps produce token-identical outputs
(absolute chunk boundaries + zero-pad-only packing).

``--tiered`` is the memory-pressure A/B for tiered KV residency
(``ServingConfig(tiered_kv=...)``): long-context requests (every prompt
far past the partial budget) are served four ways on two engines —
(a) untiered with a full-parity pool (the working-set W and decode-gap
baseline), (b) untiered with the pool shrunk to ~W/4 (admission
collapses to ~1 concurrent slot), (c) tiered-lossless on the same
shrunken pool (cold pages demote to host after each refresh, so the
pool only has to seat the hot working set — concurrency comes back at a
flat decode-gap p95, token-identical to (a)), and (d) tiered-int8 (the
quality/traffic trade: ~half the host bytes, outputs may diverge — the
mismatch count is reported).  Reports peak concurrent slots, page
high-water, decode-gap p50/p95, admission stalls/defers, and the
demote/promote/prefetch counters.

``--zero-copy`` A/Bs gathered vs page-table-routed partial KV on the
paged cache (``ServingConfig(zero_copy_partial=...)`` at the serving
layer; the ``SpecPVEngine(zero_copy=...)`` knob here): the identical
budget-straddling Poisson request set runs once with refreshes copying
the selected blocks into the dense partial buffer and once with
refreshes writing O(budget) selected-block indices and pinning the
pages (the partial body reads route through the trunk pool).  Reports
decode-step gap p50/p95, the mean decode wall time per tick class from
the scheduler's ``sched.decode.<class>`` span counters, each arm's
billed refresh HBM traffic, the pin drain check (zero pinned pages after the run), and
verifies the two arms produce token-identical outputs.

``--sampled`` A/Bs greedy vs stochastic serving through the same fused
ticks: the identical request set runs with (a) temperature-0 tree drafts,
(b) sampled chain drafts and (c) sampled tree drafts (per-request
``temperature``/``seed``/``draft`` riding on the per-slot PRNG streams).
Reports mean accept length, jitted dispatches per decode tick (pinned at
1.00 — sampling and chain masking are operands, not extra dispatches),
and decode-gap p50/p95; verifies the greedy arm stays token-identical to
solo generation and that the sampled arms replay identical token streams
when re-run (seed reproducibility).

``--sharded`` A/Bs single-host vs data-sharded serving on a forced
multi-device CPU mesh (the top-of-file XLA_FLAGS guard materialises 8
host devices before jax initialises): the identical mixed Poisson
request set runs through one unsharded paged engine and one with
``ServingConfig(mesh_shape=(data, 1))`` — slots, page tables and
per-shard page-pool ranges split over the ``data`` mesh axis while the
fused decode tick stays ONE SPMD dispatch.  Verifies token identity
(data-sharded rows are computationally independent, so sharding them is
lossless), checks the worst single host's resident pages against
pool/shards + one request's slack, pins dispatches per decode tick at
1.00, and reports the modelled per-tick cross-shard verify traffic of
the model-axis softmax-partials merge vs the gathered-block baseline
(``repro.distributed.verify_traffic_report``; >= 10x at paper scale is
the acceptance bar).

Run:  PYTHONPATH=src python benchmarks/bench_serving.py --requests 8
      PYTHONPATH=src python benchmarks/bench_serving.py --requests 8 --paged
      PYTHONPATH=src python benchmarks/bench_serving.py --requests 8 \
          --prefix-share
      PYTHONPATH=src python benchmarks/bench_serving.py --requests 8 \
          --interleave
      PYTHONPATH=src python benchmarks/bench_serving.py --requests 8 \
          --fused
      PYTHONPATH=src python benchmarks/bench_serving.py --tiered
      PYTHONPATH=src python benchmarks/bench_serving.py --sharded
"""
import os
import sys

if "--sharded" in sys.argv and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # must land before jax initialises (i.e. before the repro imports
    # below), or the forced 8-CPU-device mesh never exists
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import argparse
import time

import numpy as np

from common import ensure_dir, write_rows, RESULTS_DIR  # noqa: F401

from repro.artifacts import get_trained_pair, corpus_for
from repro.configs import SpecPVConfig
from repro.core.engine import SpecPVEngine, request_token_need
from repro.core.tree import TreeSpec
from repro.data import continuation_task
from repro.serving import Request, ServingEngine, ServingConfig
from repro.serving.scheduler import ContinuousScheduler, trim_output


def make_requests(corpus, contexts, n, rate, rng, max_new):
    """Mixed-length requests with Poisson (exponential-gap) arrival
    offsets, identical across scheduler runs.  Generation lengths
    alternate (max_new vs max_new/2): a wave runs every member to the
    longest request's budget, so divergent max_new idles wave slots the
    same way divergent prompt lengths do."""
    reqs = []
    t = 0.0
    for i in range(n):
        ctx = contexts[i % len(contexts)]
        prompt, _ = continuation_task(corpus, batch=1, context_len=ctx,
                                      seed=1000 + i)
        t += rng.exponential(1.0 / rate) if rate > 0 else 0.0
        reqs.append((t, Request(request_id=f"req-{i}", prompt=prompt[0],
                                max_new_tokens=(max_new if i % 2
                                                else max(max_new // 2, 4)))))
    return reqs


def make_prefix_share_requests(corpus, n, rate, rng, max_new, *,
                               n_sys, sys_len, tail_len):
    """Templated-prompt workload: every request is one of `n_sys` shared
    system prompts plus a unique user tail — the multi-turn /
    shared-system-prompt traffic shape where prefix caching pays.
    Requests arrive in same-system pairs (two users hitting one template
    back to back), so in-flight neighbours share live prefixes *and*
    later arrivals re-hit prefixes cached from drained ones."""
    systems = [continuation_task(corpus, batch=1, context_len=sys_len,
                                 seed=7000 + s)[0][0] for s in range(n_sys)]
    reqs, t = [], 0.0
    for i in range(n):
        tail, _ = continuation_task(corpus, batch=1, context_len=tail_len,
                                    seed=8000 + i)
        prompt = np.concatenate([systems[(i // 2) % n_sys],
                                 tail[0]]).astype(np.int32)
        t += rng.exponential(1.0 / rate) if rate > 0 else 0.0
        reqs.append((t, Request(request_id=f"req-{i}", prompt=prompt,
                                max_new_tokens=max_new)))
    return reqs


def percentiles(xs):
    return (float(np.percentile(xs, 50)), float(np.percentile(xs, 95)))


def run_continuous(srv, reqs):
    t0 = time.time()
    for off, r in reqs:
        r.arrival_s = t0 + off
        srv.submit(r)
    outs = srv.run()
    lat = [o.latency_s for o in outs]
    return outs, time.time() - t0, lat


def run_wave(srv, reqs):
    """Wave driver with arrival gating: admit what has arrived, run one
    wave, repeat — per-request latency is completion minus arrival."""
    t0 = time.time()
    pending = [(t0 + off, r) for off, r in reqs]
    lat, outs = [], []
    while pending or srv.queue:
        now = time.time()
        for arr, r in list(pending):
            if arr <= now:
                pending.remove((arr, r))
                r.arrival_s = arr
                srv.submit(r)
        if srv.queue:
            wave_outs = srv.run_one_wave()
            lat.extend(o.latency_s for o in wave_outs)
            outs.extend(wave_outs)
        elif pending:
            time.sleep(max(min(a for a, _ in pending) - time.time(), 0.0))
    return outs, time.time() - t0, lat


def check_lossless(cfg, spec, dcfg, params, dparams, scfg, reqs, outs):
    """Every continuous output must equal solo batch-1 generation."""
    solo = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=1,
                        max_len=scfg.max_len,
                        partial_verification=scfg.partial_verification)
    by_id = {o.request_id: o for o in outs}
    for _, r in reqs:
        toks, _ = solo.generate(r.prompt[None], r.max_new_tokens,
                                eos_id=r.eos_id,
                                prefill_chunk=scfg.prefill_chunk)
        raw = toks[0]
        row = trim_output([int(x) for x in raw[raw >= 0]],
                          r.max_new_tokens, r.eos_id)
        got = by_id[r.request_id].tokens
        assert np.array_equal(got, row), \
            f"{r.request_id}: continuous {got[:8]}... != solo {row[:8]}..."
    return True


def step_gap_stats(step_log):
    """Decode-step gaps per request, pooled: for each in-flight request,
    the wall-clock spacing of its consecutive decode steps.  A blocking
    long-prompt admission shows up as one giant gap for every other
    in-flight request; interleaving bounds it."""
    times = {}
    for t, rid, _ in step_log:
        times.setdefault(rid, []).append(t)
    gaps = [g for ts in times.values() for g in np.diff(ts) if len(ts) > 1]
    return np.asarray(gaps, np.float64)


def tick_class_walls(stats):
    """{tick class: (decode ticks, mean decode wall seconds)} from the
    scheduler's ``sched.decode.<class>`` span counters."""
    out = {}
    for key, n in stats.items():
        if key.startswith("span_n.sched.decode.") and n:
            name = key[len("span_n."):]
            out[name.rsplit(".", 1)[1]] = (int(n),
                                           stats["span_s." + name] / n)
    return out


def run_interleave(args, cfg, dcfg, params, dparams, corpus, spec,
                   contexts):
    """Blocking vs interleaved chunked prefill on one engine (shared jit
    compiles): identical Poisson request set, token-identity verified."""
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=args.batch,
                       max_len=max_len, partial_verification=True,
                       paged=args.paged,
                       num_pages=args.num_pages or None)
    budget = args.prefill_budget
    print(f"interleave A/B: {args.requests} requests, contexts {contexts}, "
          f"chunk 64, prefill budget {budget} tokens/tick"
          + (" (paged)" if args.paged else ""))
    if not args.no_warmup:
        warm = ContinuousScheduler(eng, prefill_chunk=64)
        for j, ctx in enumerate({min(contexts), max(contexts)}):
            prompt, _ = continuation_task(corpus, batch=1, context_len=ctx,
                                          seed=1)
            warm.submit(Request(request_id=f"warm-{j}", prompt=prompt[0],
                                max_new_tokens=8))
        warm.run()

    results = {}
    for mode, b in (("blocking", None), ("interleaved", budget)):
        # step_log is recorded inside tick() itself, so the stock run()
        # loop (arrival gating included) drives the measurement
        sched = ContinuousScheduler(eng, prefill_chunk=64,
                                    prefill_budget=b, record_steps=True)
        t0 = time.time()
        for off, r in reqs:
            sched.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off))
        outs = sched.run()
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        lat50, lat95 = percentiles([o.latency_s for o in outs])
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps)
        results[mode] = dict(outs=outs, tput=toks / wall, lat50=lat50,
                             lat95=lat95, g50=g50, g95=g95,
                             jitter=g95 - g50)
        print(f"{mode:>12}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s, request latency p50={lat50:.2f}s "
              f"p95={lat95:.2f}s")
        print(f"{'':>12}  decode-step gap p50={g50 * 1e3:.1f}ms "
              f"p95={g95 * 1e3:.1f}ms, jitter (p95-p50) = "
              f"{(g95 - g50) * 1e3:.1f}ms over {gaps.size} gaps")

    if not args.no_check:
        blk = {o.request_id: o.tokens for o in results["blocking"]["outs"]}
        for o in results["interleaved"]["outs"]:
            assert np.array_equal(o.tokens, blk[o.request_id]), \
                f"{o.request_id}: interleaved != blocking"
        print("losslessness: interleaved outputs token-identical to "
              "blocking admission")
    rb, ri = results["blocking"], results["interleaved"]
    print(f"decode-gap p95: {ri['g95'] * 1e3:.1f}ms interleaved vs "
          f"{rb['g95'] * 1e3:.1f}ms blocking "
          f"({rb['g95'] / max(ri['g95'], 1e-9):.2f}x lower)")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_interleave.csv",
               ["mode", "tok_s", "lat_p50_s", "lat_p95_s",
                "gap_p50_ms", "gap_p95_ms", "jitter_ms"],
               [[m, f"{r['tput']:.2f}", f"{r['lat50']:.2f}",
                 f"{r['lat95']:.2f}", f"{r['g50'] * 1e3:.2f}",
                 f"{r['g95'] * 1e3:.2f}", f"{r['jitter'] * 1e3:.2f}"]
                for m, r in results.items()])


def run_fused(args, cfg, dcfg, params, dparams, corpus, spec, contexts):
    """Grouped-per-mode vs fused decode ticks on one engine (shared jit
    compiles): the identical mixed Poisson request set straddles the
    partial budget, so in-flight slots routinely want different SpecPV
    modes in the same tick.  Grouped scheduling pays one batch-wide
    masked dispatch per distinct mode; the fused step folds the whole
    mode mix into one.  Reports the distinct-modes-per-tick histogram,
    jitted dispatches per decode tick, per-mode stepped rows, and
    decode-step gap p50/p95 — outputs are verified token-identical."""
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=args.batch,
                       max_len=max_len, partial_verification=True,
                       paged=args.paged,
                       num_pages=args.num_pages or None)
    print(f"fused A/B: {args.requests} requests, contexts {contexts} "
          f"(partial budget {spec.partial_budget_tokens} tokens), "
          f"batch {args.batch}" + (" (paged)" if args.paged else ""))
    if not args.no_warmup:
        # warm BOTH scheduling paths on the exact timed request set (all
        # arrivals immediate): grouped ticks compile the uniform step
        # variants, fused ticks compile every mode-MIX variant the real
        # schedule will produce — otherwise one arm pays first-compiles
        # inside its timed region.  (Each ContinuousScheduler boot
        # resets the paged engine's allocators and prefix cache, so no
        # KV state leaks between warmup and the timed arms, or between
        # the arms.)
        for f in (False, True):
            warm = ContinuousScheduler(eng, prefill_chunk=64, fused=f)
            for _, r in reqs:
                warm.submit(Request(request_id=f"warm-{r.request_id}",
                                    prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens))
            warm.run()

    results = {}
    for mode, fused in (("grouped", False), ("fused", True)):
        sched = ContinuousScheduler(eng, prefill_chunk=64, fused=fused,
                                    record_steps=True)
        t0 = time.time()
        for off, r in reqs:
            sched.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off))
        outs = sched.run()
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        dispatches = int(sched.stats["steps"])
        hist = {int(k.rsplit("_", 1)[1]): int(v)
                for k, v in sched.stats.items()
                if k.startswith("ticks_modes_")}
        ticks = max(sum(hist.values()), 1)
        mode_rows = {k[len("mode_rows_"):]: int(v)
                     for k, v in sched.stats.items()
                     if k.startswith("mode_rows_")}
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps) if gaps.size else (0.0, 0.0)
        results[mode] = dict(outs=outs, tput=toks / wall,
                             dispatches=dispatches, ticks=ticks,
                             hist=hist, mode_rows=mode_rows,
                             g50=g50, g95=g95)
        print(f"{mode:>8}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s; {dispatches} dispatches over "
              f"{ticks} decode ticks ({dispatches / ticks:.2f}/tick)")
        print(f"{'':>8}  distinct-modes-per-tick histogram: "
              + ", ".join(f"{k} mode{'s' if k > 1 else ''}: {hist[k]}"
                          for k in sorted(hist))
              + f"; mode rows: {mode_rows}")
        print(f"{'':>8}  decode-step gap p50={g50 * 1e3:.1f}ms "
              f"p95={g95 * 1e3:.1f}ms over {gaps.size} gaps")

    if not args.no_check:
        grp = {o.request_id: o.tokens for o in results["grouped"]["outs"]}
        for o in results["fused"]["outs"]:
            assert np.array_equal(o.tokens, grp[o.request_id]), \
                f"{o.request_id}: fused != grouped"
        print("losslessness: fused outputs token-identical to grouped "
              "per-mode scheduling")
    rg, rf = results["grouped"], results["fused"]
    print(f"dispatches/tick: {rf['dispatches'] / rf['ticks']:.2f} fused vs "
          f"{rg['dispatches'] / rg['ticks']:.2f} grouped "
          f"({rg['dispatches'] / max(rf['dispatches'], 1):.2f}x fewer "
          f"dispatches); decode-gap p95 "
          f"{rf['g95'] * 1e3:.1f}ms vs {rg['g95'] * 1e3:.1f}ms")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_fused.csv",
               ["mode", "tok_s", "dispatches", "decode_ticks",
                "dispatches_per_tick", "gap_p50_ms", "gap_p95_ms",
                "ticks_1_mode", "ticks_2_modes", "ticks_3_modes"],
               [[m, f"{r['tput']:.2f}", r["dispatches"], r["ticks"],
                 f"{r['dispatches'] / r['ticks']:.3f}",
                 f"{r['g50'] * 1e3:.2f}", f"{r['g95'] * 1e3:.2f}",
                 r["hist"].get(1, 0), r["hist"].get(2, 0),
                 r["hist"].get(3, 0)]
                for m, r in results.items()])


class _PrefillTraceScheduler(ContinuousScheduler):
    """ContinuousScheduler + the two measurements the prefill-batch A/B
    needs: per-request admission-to-first-token (admit to finalize) and
    the number of ticks that made prefill progress (denominator for
    dispatches/tick)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.ttft = {}                  # request_id -> seconds
        self.prefill_ticks = 0

    def _pump_prefill(self):
        n = super()._pump_prefill()
        if n:
            self.prefill_ticks += 1
        return n

    def _finalize_prefill(self, i):
        s = self.slots[i]
        super()._finalize_prefill(i)
        self.ttft[s.req.request_id] = self.clock() - s.admit_s


def run_prefill_batch(args, cfg, dcfg, params, dparams, corpus, spec,
                      contexts):
    """Serial vs fused prefill pump on a long-prompt burst (one engine,
    shared jit compiles): identical request set, token-identity
    verified.  The burst opens several cursors at once, so the serial
    pump pays one jitted dispatch per open cursor per round while the
    fused pump folds the whole row set into one."""
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=args.batch,
                       max_len=max_len, partial_verification=True,
                       paged=args.paged,
                       num_pages=args.num_pages or None)
    budget = args.prefill_budget
    print(f"prefill-batch A/B: {args.requests} long prompts (contexts "
          f"{contexts}) bursting into {args.batch} slots, chunk 64, "
          f"prefill budget {budget} tokens/tick"
          + (" (paged)" if args.paged else ""))
    if not args.no_warmup:
        # replay the set through both pumps so each arm's jit variants
        # (serial batch-1 chunks AND every fused (K, Tmax) shape the
        # schedule produces) compile outside the timed region
        for fp in (False, True):
            warm = ContinuousScheduler(eng, prefill_chunk=64,
                                       prefill_budget=budget,
                                       fused_prefill=fp)
            for _, r in reqs:
                warm.submit(Request(request_id=f"warm-{r.request_id}",
                                    prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens))
            warm.run()

    results = {}
    for mode, fp in (("serial", False), ("fused", True)):
        sched = _PrefillTraceScheduler(eng, prefill_chunk=64,
                                       prefill_budget=budget,
                                       fused_prefill=fp,
                                       record_steps=True)
        t0 = time.time()
        for off, r in reqs:
            sched.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off))
        outs = sched.run()
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        disp = int(sched.stats["prefill_dispatches"])
        ticks = max(sched.prefill_ticks, 1)
        t50, t95 = percentiles(list(sched.ttft.values()))
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps) if gaps.size else (0.0, 0.0)
        results[mode] = dict(outs=outs, tput=toks / wall, disp=disp,
                             ticks=ticks, t50=t50, t95=t95,
                             g50=g50, g95=g95)
        print(f"{mode:>8}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s; {disp} prefill dispatches over "
              f"{ticks} prefill ticks ({disp / ticks:.2f}/tick)")
        print(f"{'':>8}  admission-to-first-token p50={t50:.2f}s "
              f"p95={t95:.2f}s; decode-step gap p50={g50 * 1e3:.1f}ms "
              f"p95={g95 * 1e3:.1f}ms over {gaps.size} gaps")

    if not args.no_check:
        ser = {o.request_id: o.tokens for o in results["serial"]["outs"]}
        for o in results["fused"]["outs"]:
            assert np.array_equal(o.tokens, ser[o.request_id]), \
                f"{o.request_id}: fused prefill != serial prefill"
        print("losslessness: fused-prefill outputs token-identical to "
              "the serial pump")
    rs, rf = results["serial"], results["fused"]
    print(f"prefill dispatches/tick: {rf['disp'] / rf['ticks']:.2f} fused "
          f"vs {rs['disp'] / rs['ticks']:.2f} serial "
          f"({rs['disp'] / max(rf['disp'], 1):.2f}x fewer launches); "
          f"admission-to-first-token p95 {rf['t95']:.2f}s vs "
          f"{rs['t95']:.2f}s; decode-gap p95 {rf['g95'] * 1e3:.1f}ms vs "
          f"{rs['g95'] * 1e3:.1f}ms")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_prefill_batch.csv",
               ["mode", "tok_s", "prefill_dispatches", "prefill_ticks",
                "dispatches_per_tick", "ttft_p50_s", "ttft_p95_s",
                "gap_p50_ms", "gap_p95_ms"],
               [[m, f"{r['tput']:.2f}", r["disp"], r["ticks"],
                 f"{r['disp'] / r['ticks']:.3f}", f"{r['t50']:.2f}",
                 f"{r['t95']:.2f}", f"{r['g50'] * 1e3:.2f}",
                 f"{r['g95'] * 1e3:.2f}"]
                for m, r in results.items()])


def run_tiered(args, cfg, dcfg, params, dparams, corpus, spec, contexts):
    """Tiered-residency memory-pressure A/B (see module docstring): the
    same long-context Poisson request set through (a) untiered/parity
    pool, (b) untiered/shrunken pool, (c) tiered-lossless/shrunken,
    (d) tiered-int8/shrunken.  Two engines total: (b) swaps (a)'s trunk
    allocator, (d) flips (c)'s quantization — so each pair shares its
    jit compiles and the arms differ only in residency policy."""
    from repro.kvcache.cache import PageAllocator

    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    nb_seq = -(-max_len // spec.block_size)
    parity = args.batch * nb_seq + 1
    emax = TreeSpec.from_branch(dcfg.tree_branch[: dcfg.tree_depth]).max_path
    need_max = -(-request_token_need(max(contexts), args.max_new,
                                     spec.buffer_size, emax)
                 // spec.block_size)
    print(f"tiered A/B: {args.requests} requests, contexts {contexts} "
          f"(all past the {spec.partial_budget_tokens}-token partial "
          f"budget), batch {args.batch}, max_new {args.max_new}; "
          f"largest request needs {need_max} pages")

    def build(tiered):
        return SpecPVEngine(cfg, spec, dcfg, params, dparams,
                            batch=args.batch, max_len=max_len,
                            partial_verification=True, paged=True,
                            num_pages=(parity if not tiered else small[0]),
                            num_draft_pages=parity, prefix_cache=False,
                            tiered=tiered, tier_lossless=True)

    def drive(eng, label, warm=True):
        if warm and not args.no_warmup:
            # replay the whole set once so every fused mode-mix variant
            # the real schedule produces is compiled outside the timed
            # region (the scheduler boot resets allocators afterwards)
            warm = ContinuousScheduler(eng, prefill_chunk=64,
                                       prefill_budget=args.prefill_budget)
            for _, r in reqs:
                warm.submit(Request(request_id=f"warm-{r.request_id}",
                                    prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens))
            warm.run()
        tier0 = eng.tier_stats()
        if eng.tiered:      # per-run peak (deltas can't subtract a max)
            eng._tier.host_bytes_peak = 0
        # chunked-prefill interleaving in every arm: under memory pressure
        # admissions happen mid-run, and a blocking 700+-token prefill
        # would dominate the decode-gap tail for reasons unrelated to
        # residency (exactly the PR-4 jitter --interleave measures)
        sched = ContinuousScheduler(eng, prefill_chunk=64,
                                    prefill_budget=args.prefill_budget,
                                    record_steps=True)
        t0 = time.time()
        for off, r in reqs:
            sched.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off))
        outs = sched.run()
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps) if gaps.size else (0.0, 0.0)
        ps = eng.page_stats()
        tier = {k: v - tier0.get(k, 0) for k, v in eng.tier_stats().items()}
        if eng.tiered:
            tier["tier_host_bytes_peak"] = \
                eng.tier_stats()["tier_host_bytes_peak"]
        r = dict(outs={o.request_id: o.tokens for o in outs},
                 tput=toks / wall, g50=g50, g95=g95,
                 peak=int(sched.stats.get("peak_active", 0)),
                 stalls=int(sched.stats.get("page_stalls", 0)),
                 defers=int(sched.stats.get("tier_defers", 0)),
                 hw=ps["resident_high_water"], cap=ps["capacity"],
                 tier=tier)
        print(f"{label:>16}: {toks} tokens in {wall:.1f}s -> "
              f"{r['tput']:.1f} tok/s; peak concurrent slots {r['peak']}, "
              f"pages high-water {r['hw']}/{r['cap']}, decode-gap "
              f"p50={g50 * 1e3:.1f}ms p95={g95 * 1e3:.1f}ms, "
              f"stalls {r['stalls']}, defers {r['defers']}")
        if tier.get("tier_demoted_pages"):
            print(f"{'':>16}  tier: demoted {tier['tier_demoted_pages']} / "
                  f"promoted {tier['tier_promoted_pages']} pages, prefetch "
                  f"hits {tier['tier_prefetch_hits']}, sync promotes "
                  f"{tier['tier_sync_promotes']}, host bytes peak "
                  f"{tier['tier_host_bytes_peak'] / 2 ** 20:.2f}MiB")
        return r

    results = {}
    small = [0]                                    # filled after baseline
    eng_flat = build(tiered=False)
    results["untiered/parity"] = drive(eng_flat, "untiered/parity")
    W = results["untiered/parity"]["hw"]
    small[0] = max(int(np.ceil(W / args.tier_shrink)), need_max + 2) + 1
    shrink = W / (small[0] - 1)
    print(f"working set W = {W} pages -> shrunken pool "
          f"{small[0] - 1} usable ({shrink:.1f}x below W)")
    eng_flat._page_alloc = PageAllocator(small[0])
    try:
        results["untiered/small"] = drive(eng_flat, "untiered/small",
                                          warm=False)
    finally:
        eng_flat._page_alloc = PageAllocator(parity)

    eng_tier = build(tiered=True)
    results["tiered/small"] = drive(eng_tier, "tiered-lossless/small")
    if not args.skip_int8:
        eng_tier._tier.lossless = False
        results["tiered-int8/small"] = drive(eng_tier, "tiered-int8/small",
                                             warm=False)

    base = results["untiered/parity"]
    mism = {}
    for name in ("untiered/small", "tiered/small", "tiered-int8/small"):
        if name in results:
            mism[name] = sum(
                not np.array_equal(toks, base["outs"][rid])
                for rid, toks in results[name]["outs"].items())
    if not args.no_check:
        assert mism["tiered/small"] == 0, \
            "tiered-lossless outputs diverged from the untiered baseline"
        print("losslessness: tiered-lossless outputs token-identical to "
              "the untiered parity-pool baseline")
    if "tiered-int8/small" in results:
        print(f"int8 quality delta: {mism['tiered-int8/small']}"
              f"/{args.requests} requests diverge from the baseline")
    rt, rs = results["tiered/small"], results["untiered/small"]
    print(f"headline: {shrink:.1f}x smaller pool holds "
          f"{rt['peak']} concurrent long-context slots vs "
          f"{rs['peak']} untiered "
          f"({rt['peak'] / max(rs['peak'], 1):.1f}x more) at decode-gap "
          f"p95 {rt['g95'] * 1e3:.1f}ms vs baseline "
          f"{base['g95'] * 1e3:.1f}ms "
          f"({rt['g95'] / max(base['g95'], 1e-9):.2f}x)")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_tiered.csv",
               ["mode", "usable_pages", "tok_s", "peak_active",
                "resident_high_water", "gap_p50_ms", "gap_p95_ms",
                "page_stalls", "tier_defers", "demoted", "promoted",
                "prefetch_hits", "sync_promotes", "mismatched_requests"],
               [[m, r["cap"], f"{r['tput']:.2f}", r["peak"], r["hw"],
                 f"{r['g50'] * 1e3:.2f}", f"{r['g95'] * 1e3:.2f}",
                 r["stalls"], r["defers"],
                 r["tier"].get("tier_demoted_pages", 0),
                 r["tier"].get("tier_promoted_pages", 0),
                 r["tier"].get("tier_prefetch_hits", 0),
                 r["tier"].get("tier_sync_promotes", 0),
                 mism.get(m, 0)]
                for m, r in results.items()])


def run_sampled(args, cfg, dcfg, params, dparams, corpus, spec, contexts):
    """Greedy vs sampled-chain vs sampled-tree serving on one engine
    (shared jit compiles): the identical mixed Poisson request set runs
    three times — (a) greedy tree drafts (temperature 0, the PR-8
    baseline), (b) stochastic chain drafts, (c) stochastic tree drafts
    (both at --temperature, per-request seeds, speculative-sampling
    acceptance).  Every arm's ticks must stay ONE jitted dispatch
    (sampling and chain masking ride as operands, not control flow).
    Reports mean accept length, dispatches/tick, decode-gap p50/p95, and
    verifies (i) the greedy arm is token-identical to solo batch-1
    generation and (ii) the sampled arms replay identical token streams
    when re-run (seed reproducibility)."""
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=args.batch,
                       max_len=max_len, partial_verification=True)
    temp = args.temperature
    print(f"sampled A/B: {args.requests} requests, contexts {contexts} "
          f"(partial budget {spec.partial_budget_tokens} tokens), "
          f"batch {args.batch}, temperature {temp}")

    arms = (("greedy-tree", 0.0, "tree"),
            ("sampled-chain", temp, "chain"),
            ("sampled-tree", temp, "tree"))

    def submit_all(sched, t0, prefix=""):
        for off, r in sched_reqs:
            sched.submit(Request(request_id=prefix + r.request_id,
                                 prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off,
                                 temperature=arm_temp, seed=arm_seed(r),
                                 draft=arm_draft))
        return sched.run()

    def arm_seed(r):
        return args.seed * 1000 + int(r.request_id.rsplit("-", 1)[1])

    results = {}
    for name, arm_temp, arm_draft in arms:
        sched_reqs = reqs
        if not args.no_warmup:
            # replay the arm's exact request set so its jit variants
            # (mode mix x sampled x chain flags) compile outside the
            # timed region
            warm = ContinuousScheduler(eng, prefill_chunk=64)
            submit_all(warm, time.time(), prefix="warm-")
        sched = ContinuousScheduler(eng, prefill_chunk=64,
                                    record_steps=True)
        t0 = time.time()
        outs = submit_all(sched, t0)
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        dispatches = int(sched.stats["steps"])
        ticks = max(sum(int(v) for k, v in sched.stats.items()
                        if k.startswith("ticks_modes_")), 1)
        accept = float(np.mean([o.mean_accept for o in outs]))
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps) if gaps.size else (0.0, 0.0)
        results[name] = dict(outs=outs, tput=toks / wall, accept=accept,
                             dispatches=dispatches, ticks=ticks,
                             g50=g50, g95=g95)
        print(f"{name:>14}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s; mean accept {accept:.2f}; "
              f"{dispatches} dispatches over {ticks} decode ticks "
              f"({dispatches / ticks:.2f}/tick); decode-gap "
              f"p50={g50 * 1e3:.1f}ms p95={g95 * 1e3:.1f}ms")
        assert dispatches == ticks, \
            f"{name}: {dispatches} dispatches over {ticks} ticks"

    if not args.no_check:
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True)
        greedy_reqs = [(off, Request(request_id=r.request_id,
                                     prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens,
                                     eos_id=r.eos_id))
                       for off, r in reqs]
        check_lossless(cfg, spec, dcfg, params, dparams, scfg, greedy_reqs,
                       results["greedy-tree"]["outs"])
        print("losslessness: greedy arm token-identical to single-request "
              "generation")
        # seed reproducibility: a sampled re-run replays the same streams
        for name, arm_temp, arm_draft in arms[1:]:
            sched_reqs = reqs
            sched = ContinuousScheduler(eng, prefill_chunk=64)
            redo = {o.request_id: o.tokens
                    for o in submit_all(sched, time.time())}
            for o in results[name]["outs"]:
                assert np.array_equal(o.tokens, redo[o.request_id]), \
                    f"{name}/{o.request_id}: sampled re-run diverged"
        print("reproducibility: sampled arms replay identical token "
              "streams from their request seeds")

    rg = results["greedy-tree"]
    rt = results["sampled-tree"]
    print(f"headline: sampled-tree accept {rt['accept']:.2f} vs chain "
          f"{results['sampled-chain']['accept']:.2f} vs greedy "
          f"{rg['accept']:.2f}; dispatches/tick 1.00 in every arm; "
          f"decode-gap p95 {rt['g95'] * 1e3:.1f}ms sampled-tree vs "
          f"{rg['g95'] * 1e3:.1f}ms greedy")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_sampled.csv",
               ["arm", "temperature", "draft", "tok_s", "mean_accept",
                "dispatches", "decode_ticks", "dispatches_per_tick",
                "gap_p50_ms", "gap_p95_ms"],
               [[name, t, d, f"{r['tput']:.2f}", f"{r['accept']:.3f}",
                 r["dispatches"], r["ticks"],
                 f"{r['dispatches'] / r['ticks']:.3f}",
                 f"{r['g50'] * 1e3:.2f}", f"{r['g95'] * 1e3:.2f}"]
                for (name, t, d), r in zip(arms, results.values())])


def run_zero_copy(args, cfg, dcfg, params, dparams, corpus, spec,
                  contexts):
    """Gathered vs page-table-routed (zero-copy) partial KV on the same
    mixed Poisson request set straddling the partial budget (so slots
    routinely refresh and decode partially).  Two paged engines — the
    zero_copy flag changes the EngineState layout, so the arms cannot
    share jit compiles; each warms on the identical request set.  A
    gathered refresh copies the selected blocks' bytes into the dense
    per-slot partial buffer; a routed refresh writes O(budget) selected
    block indices and pins the pages.  Reports decode-step gap p50/p95,
    the per-class mean tick wall time (refresh ticks are the ones the
    tentpole targets), modelled refresh HBM
    traffic of each billing contract, and the pin high-water/drain —
    outputs are verified token-identical."""
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    nb_seq = -(-max_len // spec.block_size)
    emax = TreeSpec.from_branch(dcfg.tree_branch[: dcfg.tree_depth]).max_path
    need_max = -(-request_token_need(max(contexts), args.max_new,
                                     spec.buffer_size, emax)
                 // spec.block_size)
    num_pages = (args.num_pages
                 or max((args.batch * nb_seq * 3) // 5, need_max + 1) + 1)
    print(f"zero-copy A/B: {args.requests} requests, contexts {contexts} "
          f"(partial budget {spec.partial_budget_tokens} tokens), "
          f"batch {args.batch}, paged pool {num_pages - 1} usable pages")

    results = {}
    for mode, zc in (("gathered", False), ("routed", True)):
        eng = SpecPVEngine(cfg, spec, dcfg, params, dparams,
                           batch=args.batch, max_len=max_len,
                           partial_verification=True, paged=True,
                           num_pages=num_pages, zero_copy=zc)
        if not args.no_warmup:
            # replay the exact request set so every fused mode-mix jit
            # variant this arm's schedule produces compiles outside the
            # timed region
            warm = ContinuousScheduler(eng, prefill_chunk=64)
            for _, r in reqs:
                warm.submit(Request(request_id=f"warm-{r.request_id}",
                                    prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens))
            warm.run()
            # bill only the timed run's refresh traffic
            eng.traffic.bytes_by_mode.clear()
            eng.traffic.steps_by_mode.clear()
        sched = ContinuousScheduler(eng, prefill_chunk=64,
                                    record_steps=True)
        t0 = time.time()
        for off, r in reqs:
            sched.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id, arrival_s=t0 + off))
        outs = sched.run()
        wall = time.time() - t0
        toks = sum(len(o.tokens) for o in outs)
        gaps = step_gap_stats(sched.step_log)
        g50, g95 = percentiles(gaps) if gaps.size else (0.0, 0.0)
        walls = tick_class_walls(sched.stats)
        ps = eng.page_stats()
        pins = int(ps.get("pinned_pages", 0))
        rbytes = int(eng.traffic.bytes_by_mode.get("refresh", 0))
        results[mode] = dict(outs=outs, tput=toks / wall, g50=g50,
                             g95=g95, walls=walls,
                             pins=pins, rbytes=rbytes)
        print(f"{mode:>9}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s; decode-gap p50={g50 * 1e3:.1f}ms "
              f"p95={g95 * 1e3:.1f}ms")
        print(f"{'':>9}  tick wall by class: "
              + ", ".join(f"{c}: {n}x {m * 1e3:.1f}ms"
                          for c, (n, m) in sorted(walls.items()))
              + f"; billed refresh traffic {rbytes / 2**20:.2f}MiB; "
              f"pinned pages after drain: {pins}")
        if zc:
            assert pins == 0, \
                f"routed arm leaked {pins} pinned pages after drain"

    if not args.no_check:
        gat = {o.request_id: o.tokens for o in results["gathered"]["outs"]}
        for o in results["routed"]["outs"]:
            assert np.array_equal(o.tokens, gat[o.request_id]), \
                f"{o.request_id}: routed != gathered"
        print("losslessness: zero-copy (routed) outputs token-identical "
              "to the gathered-partial baseline")
    rg, rr = results["gathered"], results["routed"]
    rmean = {m: r["walls"].get("refresh", (0, 0.0)) for m, r in
             results.items()}
    print(f"refresh-tick mean: {rmean['routed'][1] * 1e3:.1f}ms routed vs "
          f"{rmean['gathered'][1] * 1e3:.1f}ms gathered; billed refresh "
          f"traffic {rg['rbytes'] / max(rr['rbytes'], 1):.2f}x smaller "
          f"routed (rebuild-term model: benchmarks/bench_fig6_refresh.py)")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_zero_copy.csv",
               ["mode", "tok_s", "gap_p50_ms", "gap_p95_ms",
                "refresh_tick_mean_ms", "refresh_ticks", "refresh_bytes",
                "pinned_pages_after"],
               [[m, f"{r['tput']:.2f}", f"{r['g50'] * 1e3:.2f}",
                 f"{r['g95'] * 1e3:.2f}", f"{rmean[m][1] * 1e3:.2f}",
                 rmean[m][0], r["rbytes"], r["pins"]]
                for m, r in results.items()])


def run_prefix_share(args, cfg, dcfg, params, dparams, corpus, spec):
    """Shared-system-prompt workload: paged continuous scheduler with the
    copy-on-write prefix cache on vs off (identical request set)."""
    rng = np.random.default_rng(args.seed)
    reqs = make_prefix_share_requests(
        corpus, args.requests, args.rate, rng, args.max_new,
        n_sys=args.num_sys, sys_len=args.sys_len, tail_len=args.tail_len)
    max_len = args.sys_len + args.tail_len + args.max_new + 128
    bs = spec.block_size
    print(f"prefix-share workload: {args.requests} requests over "
          f"{args.num_sys} system prompts of {args.sys_len} tokens "
          f"({args.sys_len // bs} full blocks of {bs})")

    results = {}
    for share in (False, True):
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True,
                             paged_kv=True, num_pages=args.num_pages or None,
                             prefix_cache=share)
        srv = ServingEngine(cfg, spec, dcfg, params, dparams, scfg)
        if not args.no_warmup:
            prompt, _ = continuation_task(corpus, batch=1,
                                          context_len=args.sys_len, seed=1)
            srv.submit(Request(request_id="warm", prompt=prompt[0],
                               max_new_tokens=8))
            srv.run()
            # warmup must not seed the cache, the hit counters, or the
            # high-water marks — only the jit compiles should survive
            srv.reset_warm()
        run_reqs = [(off, Request(request_id=r.request_id, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  eos_id=r.eos_id))
                    for off, r in reqs]
        outs, wall, lat = run_continuous(srv, run_reqs)
        toks = sum(len(o.tokens) for o in outs)
        p50, p95 = percentiles(lat)
        ps, pf = srv.page_stats(), srv.prefix_stats()
        name = "share" if share else "no-share"
        results[name] = dict(outs=outs, reqs=run_reqs, tput=toks / wall,
                             p50=p50, p95=p95, hw=ps["high_water"],
                             rhw=ps["resident_high_water"], pf=pf,
                             cap=ps["capacity"], blk=ps["block_size"])
        print(f"{name:>10}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s, latency p50={p50:.1f}s "
              f"p95={p95:.1f}s, committed pages high-water "
              f"{ps['high_water']}/{ps['capacity']} (resident incl. idle "
              f"cached: {ps['resident_high_water']})")
        if share:
            hit = pf["blocks_matched"] / max(pf["blocks_seen"], 1)
            # working-set saving: peak pages live requests could not do
            # without (idle cached pages are reclaimable, reported above)
            saved = results["no-share"]["hw"] - ps["high_water"]
            print(f"{'':>10}  prefix-cache hit rate: {hit:.0%} "
                  f"({pf['blocks_matched']}/{pf['blocks_seen']} blocks), "
                  f"prefill tokens skipped: "
                  f"{pf['prefill_tokens_skipped']}, committed pages "
                  f"saved by sharing: {saved} "
                  f"({saved * ps['block_size']} tokens)")

    if not args.no_check:
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True)
        check_lossless(cfg, spec, dcfg, params, dparams, scfg,
                       results["share"]["reqs"], results["share"]["outs"])
        print("losslessness: shared-prefix outputs token-identical to "
              "single-request generation")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_prefix.csv",
               ["mode", "tok_s", "p50_s", "p95_s",
                "committed_high_water_pages", "resident_high_water_pages",
                "blocks_matched", "blocks_seen", "prefill_tokens_skipped"],
               [[m, f"{r['tput']:.2f}", f"{r['p50']:.2f}",
                 f"{r['p95']:.2f}", r["hw"], r["rhw"],
                 r["pf"].get("blocks_matched", 0),
                 r["pf"].get("blocks_seen", 0),
                 r["pf"].get("prefill_tokens_skipped", 0)]
                for m, r in results.items()])


def run_sharded(args, cfg, dcfg, params, dparams, corpus, spec, contexts):
    """Single-host vs data-sharded serving on the forced CPU mesh: the
    identical mixed Poisson request set runs through an unsharded paged
    engine and one with ``ServingConfig(mesh_shape=(data, 1))``.  Token
    identity, the worst host's resident pages vs pool/shards + slack,
    dispatches/tick pinned at 1.00, and the modelled cross-shard verify
    traffic (merge path vs gathered blocks) are all checked here — this
    is the acceptance driver for the sharded-serving work."""
    import jax
    from repro.distributed import verify_traffic_report

    ndev = jax.device_count()
    data = max(d for d in (8, 4, 2, 1)
               if d <= ndev and args.batch % d == 0)
    if data < 2:
        print(f"sharded A/B skipped: only {ndev} device(s) visible and/or "
              f"batch {args.batch} not divisible; run with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, contexts, args.requests, args.rate, rng,
                         args.max_new)
    max_len = max(contexts) + args.max_new + 128
    nb_seq = -(-max_len // spec.block_size)
    emax = TreeSpec.from_branch(dcfg.tree_branch[: dcfg.tree_depth]).max_path
    need_max = -(-request_token_need(max(contexts), args.max_new,
                                     spec.buffer_size, emax)
                 // spec.block_size)
    # pool under pressure (below the contiguous reservation), but every
    # SHARD must seat the largest single request — the per-shard ranges
    # are what admission gates on — and the usable count rounds up to a
    # multiple of the data axis so the ranges split evenly
    usable = args.num_pages or max((args.batch * nb_seq * 3) // 5,
                                   data * (need_max + 1))
    usable += (-usable) % data
    print(f"sharded A/B: {args.requests} requests, contexts {contexts}, "
          f"batch {args.batch}, mesh ({data}, 1) over {ndev} devices, "
          f"pool {usable} usable pages ({usable // data} per shard)")

    results = {}
    for arm, mesh_shape in (("single-host", None),
                            ("data-sharded", (data, 1))):
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True,
                             paged_kv=True, num_pages=usable + 1,
                             mesh_shape=mesh_shape)
        srv = ServingEngine(cfg, spec, dcfg, params, dparams, scfg)
        if not args.no_warmup:
            # compile the fused step/prefill jits (and, for the meshed
            # arm, their SPMD partitions) outside the timed region
            for j, ctx in enumerate({min(contexts), max(contexts)}):
                prompt, _ = continuation_task(corpus, batch=1,
                                              context_len=ctx, seed=1)
                srv.submit(Request(request_id=f"warm-{j}",
                                   prompt=prompt[0], max_new_tokens=8))
            srv.run()
            srv.reset_warm()
        run_reqs = [(off, Request(request_id=r.request_id, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  eos_id=r.eos_id))
                    for off, r in reqs]
        outs, wall, lat = run_continuous(srv, run_reqs)
        toks = sum(len(o.tokens) for o in outs)
        p50, p95 = percentiles(lat)
        dispatches = int(srv.stats["steps"])
        hist = {int(k.rsplit("_", 1)[1]): int(v)
                for k, v in srv.stats.items()
                if k.startswith("ticks_modes_")}
        ticks = max(sum(hist.values()), 1)
        ps = srv.page_stats()
        results[arm] = dict(outs=outs, reqs=run_reqs, tput=toks / wall,
                            p50=p50, p95=p95, dispatches=dispatches,
                            ticks=ticks, ps=ps,
                            stalls=int(srv.stats.get("page_stalls", 0)))
        print(f"{arm:>12}: {toks} tokens in {wall:.1f}s -> "
              f"{toks / wall:.1f} tok/s; {dispatches} dispatches over "
              f"{ticks} decode ticks ({dispatches / ticks:.2f}/tick); "
              f"latency p50={p50:.1f}s p95={p95:.1f}s")
        if mesh_shape is None:
            print(f"{'':>12}  committed pages high-water: "
                  f"{ps['high_water']}/{ps['capacity']}")
        else:
            per = [int(ps[f"high_water_shard_{s}"]) for s in range(data)]
            print(f"{'':>12}  per-host pages high-water: {per} "
                  f"(worst host {int(ps['peak_pages_per_host'])}; bound "
                  f"{ps['capacity'] // data} + {nb_seq} slack; the "
                  f"single-host arm held "
                  f"{results['single-host']['ps']['high_water']})")

    rb, rs = results["single-host"], results["data-sharded"]
    base = {o.request_id: o.tokens for o in rb["outs"]}
    for o in rs["outs"]:
        assert np.array_equal(o.tokens, base[o.request_id]), \
            f"{o.request_id}: data-sharded != single-host"
    print("losslessness: data-sharded outputs token-identical to the "
          "single-host fused baseline")
    if not args.no_check:
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True)
        check_lossless(cfg, spec, dcfg, params, dparams, scfg,
                       rs["reqs"], rs["outs"])
        print("losslessness: data-sharded outputs token-identical to "
              "single-request generation")

    peak = int(rs["ps"]["peak_pages_per_host"])
    bound = rs["ps"]["capacity"] // data + nb_seq
    assert peak <= bound, \
        f"worst host's resident pages {peak} > pool/shards+slack {bound}"
    for arm, r in results.items():
        assert r["dispatches"] == r["ticks"], \
            f"{arm}: {r['dispatches']} dispatches over {r['ticks']} ticks"
    print(f"per-host residency: worst host {peak} pages <= "
          f"{rs['ps']['capacity']} pool / {data} shards + {nb_seq} slack; "
          f"dispatches/tick 1.00 both arms")

    # modelled cross-shard verify traffic of the model-axis path: the
    # softmax-partials merge vs all-gathering the selected KV blocks, at
    # paper scale (8B-class trunk, 8-way CP, 128x128-token budget) and
    # at this bench's dimensions
    dh = cfg.head_dim or cfg.d_model // cfg.num_heads
    paper = verify_traffic_report(batch=8, q_tokens=8, num_heads=32,
                                  num_kv_heads=8, head_dim=128,
                                  num_layers=32, n_shards=8,
                                  budget_blocks=128, block_size=128)
    bench = verify_traffic_report(batch=args.batch, q_tokens=emax + 1,
                                  num_heads=cfg.num_heads,
                                  num_kv_heads=cfg.num_kv_heads,
                                  head_dim=dh, num_layers=cfg.num_layers,
                                  n_shards=data,
                                  budget_blocks=spec.retrieval_budget_blocks,
                                  block_size=spec.block_size)
    assert paper["traffic_ratio"] >= 10.0, paper
    print(f"cross-shard verify traffic per tick (paper scale, 8-way CP): "
          f"merge path {paper['merged_partials_bytes'] / 2**20:.1f} MiB vs "
          f"gathered blocks {paper['gathered_blocks_bytes'] / 2**20:.1f} "
          f"MiB -> {paper['traffic_ratio']:.1f}x smaller "
          f"(bench dims: {bench['traffic_ratio']:.1f}x)")

    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving_sharded.csv",
               ["arm", "data_shards", "tok_s", "p50_s", "p95_s",
                "dispatches", "decode_ticks", "dispatches_per_tick",
                "high_water_pages", "peak_pages_per_host",
                "page_stalls", "merged_partials_bytes_paper",
                "gathered_blocks_bytes_paper", "traffic_ratio_paper"],
               [["single-host", 1, f"{rb['tput']:.2f}", f"{rb['p50']:.2f}",
                 f"{rb['p95']:.2f}", rb["dispatches"], rb["ticks"],
                 f"{rb['dispatches'] / rb['ticks']:.3f}",
                 rb["ps"]["high_water"], "", rb["stalls"], "", "", ""],
                ["data-sharded", data, f"{rs['tput']:.2f}",
                 f"{rs['p50']:.2f}", f"{rs['p95']:.2f}", rs["dispatches"],
                 rs["ticks"], f"{rs['dispatches'] / rs['ticks']:.3f}",
                 rs["ps"]["high_water"], peak, rs["stalls"],
                 paper["merged_partials_bytes"],
                 paper["gathered_blocks_bytes"],
                 f"{paper['traffic_ratio']:.2f}"]])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (req/s); 0 = all at once")
    ap.add_argument("--contexts", type=int, nargs="+", default=None,
                    help="prompt lengths cycled over (default "
                         "64 192 96 160 224; --interleave mixes in long "
                         "prompts: 64 512 96 384 224)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="include jit compilation in the timed region")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-check", action="store_true",
                    help="skip the per-request losslessness check")
    ap.add_argument("--paged", action="store_true",
                    help="paged full-KV cache for the continuous scheduler "
                         "(block pool + page-gated admission)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool size incl. the null page (0 = ~60%% of the "
                         "contiguous batch x max_len reservation)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="shared-system-prompt workload: A/B the paged "
                         "continuous scheduler with the copy-on-write "
                         "prefix cache on vs off")
    ap.add_argument("--interleave", action="store_true",
                    help="A/B blocking admission vs chunked-prefill "
                         "interleaving: decode-step gap p50/p95 + jitter")
    ap.add_argument("--fused", action="store_true",
                    help="A/B grouped-per-mode vs fused decode ticks: "
                         "distinct-modes-per-tick histogram, jitted "
                         "dispatches per tick, decode-gap p50/p95")
    ap.add_argument("--prefill-batch", action="store_true",
                    help="A/B serial vs fused prefill pump on a "
                         "long-prompt burst: prefill dispatches/tick, "
                         "admission-to-first-token p50/p95, decode-gap "
                         "p50/p95 (long-prompt burst defaults: contexts "
                         "512 448 512 384, batch 4, rate 0, budget 256)")
    ap.add_argument("--sampled", action="store_true",
                    help="A/B greedy vs sampled-chain vs sampled-tree "
                         "serving (per-request temperature/seed through "
                         "the fused tick): mean accept length, "
                         "dispatches/tick (pinned at 1.00), decode-gap "
                         "p50/p95, greedy losslessness + sampled seed "
                         "reproducibility")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="sampled: temperature of the stochastic arms")
    ap.add_argument("--sharded", action="store_true",
                    help="A/B single-host vs data-sharded serving on a "
                         "forced 8-CPU-device mesh (mesh_shape=(8, 1)): "
                         "token identity, worst-host resident pages vs "
                         "pool/shards + slack, dispatches/tick, modelled "
                         "cross-shard verify traffic (defaults: batch 8, "
                         "mode-mixing contexts 64 192 96 256 224)")
    ap.add_argument("--zero-copy", action="store_true",
                    help="A/B gathered vs page-table-routed (zero-copy) "
                         "partial KV on the paged cache: decode-gap "
                         "p50/p95, per-class mean tick wall time, billed "
                         "refresh traffic, pin drain check, token "
                         "identity")
    ap.add_argument("--tiered", action="store_true",
                    help="tiered-residency memory-pressure A/B: untiered "
                         "parity pool vs untiered + tiered (lossless and "
                         "int8) on a ~4.5x smaller pool; long-context "
                         "defaults (contexts 768 720 768 736, batch 8, "
                         "max_new 48) unless overridden")
    ap.add_argument("--tier-shrink", type=float, default=4.5,
                    help="tiered: shrink the pool to working-set/THIS "
                         "(floored at the largest single request; the "
                         "default leaves the shrunken pool below two "
                         "untiered long requests, so the untiered arm "
                         "collapses to sequential admission)")
    ap.add_argument("--skip-int8", action="store_true",
                    help="tiered: skip the int8 quality-delta arm")
    ap.add_argument("--prefill-budget", type=int, default=64,
                    help="interleave: prefill tokens per tick (>= the "
                         "64-token prefill chunk; the per-tick bound is "
                         "max(budget, chunk))")
    ap.add_argument("--num-sys", type=int, default=1,
                    help="prefix-share: distinct shared system prompts "
                         "(1 = one hot template, the canonical case; "
                         ">1 mixes templates — peak-residency savings "
                         "then need same-template requests in flight "
                         "together, though hit rate and skipped prefill "
                         "still accrue across templates)")
    ap.add_argument("--sys-len", type=int, default=96,
                    help="prefix-share: system-prompt tokens (block-"
                         "aligned prefixes share; 16-token blocks)")
    ap.add_argument("--tail-len", type=int, default=48,
                    help="prefix-share: unique user-tail tokens")
    args = ap.parse_args()

    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                        retrieval_budget_blocks=4, local_window_blocks=2,
                        buffer_size=48)
    if args.prefix_share:
        run_prefix_share(args, cfg, dcfg, params, dparams, corpus, spec)
        return
    if args.interleave:
        contexts = args.contexts or [64, 512, 96, 384, 224]
        run_interleave(args, cfg, dcfg, params, dparams, corpus, spec,
                       contexts)
        return
    if args.fused:
        # straddle the partial budget so in-flight slots diverge:
        # short prompts stay in Full, long ones cycle Refresh/Partial
        contexts = args.contexts or [64, 192, 96, 256, 224]
        run_fused(args, cfg, dcfg, params, dparams, corpus, spec, contexts)
        return
    if args.sampled:
        # straddle the partial budget so sampled acceptance runs under
        # every verify mode, not just Full
        contexts = args.contexts or [64, 192, 96, 256, 224]
        run_sampled(args, cfg, dcfg, params, dparams, corpus, spec,
                    contexts)
        return
    if args.prefill_batch:
        # long prompts, bursty arrivals: several cursors must be open at
        # once or the serial and fused pumps degenerate to the same
        # schedule.  rate 0 queues the whole burst at t0; the budget
        # covers ~4 chunks so a fused round packs the full row set.
        contexts = args.contexts or [512, 448, 512, 384]
        if args.batch == ap.get_default("batch"):
            args.batch = 4
        if args.rate == ap.get_default("rate"):
            args.rate = 0.0
        if args.prefill_budget == ap.get_default("prefill_budget"):
            args.prefill_budget = 256
        run_prefill_batch(args, cfg, dcfg, params, dparams, corpus, spec,
                          contexts)
        return
    if args.sharded:
        # straddle the partial budget (like --fused) so the meshed tick
        # carries a real mode mix; batch 8 fills the 8-way data axis
        # one slot per shard
        contexts = args.contexts or [64, 192, 96, 256, 224]
        if args.batch == ap.get_default("batch"):
            args.batch = 8
        run_sharded(args, cfg, dcfg, params, dparams, corpus, spec,
                    contexts)
        return
    if args.zero_copy:
        # straddle the partial budget (like --fused) so slots refresh
        # and decode partially — the modes the tentpole changes
        contexts = args.contexts or [64, 192, 96, 256, 224]
        run_zero_copy(args, cfg, dcfg, params, dparams, corpus, spec,
                      contexts)
        return
    if args.tiered:
        # long contexts only, and near-uniform: each prompt's cold pages
        # (prompt // block) must dwarf its hot partial working set, or a
        # pool shrink has nothing to demote its way out of — and a much
        # shorter straggler would still fit the shrunken pool untiered,
        # muddying the concurrency collapse the A/B demonstrates.
        # max_new long enough for a second refresh, so the promote +
        # prefetch path runs in-band.
        contexts = args.contexts or [768, 720, 768, 736]
        if args.batch == ap.get_default("batch"):
            args.batch = 8
        if args.max_new == ap.get_default("max_new"):
            args.max_new = 48
        if args.prefill_budget == ap.get_default("prefill_budget"):
            # a pumping cursor pins its whole page bill until its first
            # refresh-demotion, deferring every debt-holding refresh row
            # meanwhile — a larger per-tick budget keeps that admission
            # window to a few ticks instead of a dozen
            args.prefill_budget = 256
        run_tiered(args, cfg, dcfg, params, dparams, corpus, spec, contexts)
        return
    args.contexts = args.contexts or [64, 192, 96, 160, 224]
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(corpus, args.contexts, args.requests, args.rate,
                         rng, args.max_new)
    max_len = max(args.contexts) + args.max_new + 128

    nb_seq = -(-max_len // spec.block_size)
    num_pages = None
    if args.paged:
        # pool under memory pressure: well below the contiguous
        # batch x nb_seq reservation, but with headroom for the largest
        # single request (otherwise it would be rejected outright) —
        # sized by the engine's own token-need formula
        emax = TreeSpec.from_branch(
            dcfg.tree_branch[: dcfg.tree_depth]).max_path
        need_max = -(-request_token_need(max(args.contexts), args.max_new,
                                         spec.buffer_size, emax)
                     // spec.block_size)
        num_pages = (args.num_pages
                     or max((args.batch * nb_seq * 3) // 5, need_max + 1) + 1)
        print(f"paged pool: {num_pages - 1} usable pages of "
              f"{spec.block_size} tokens (contiguous would reserve "
              f"{args.batch * nb_seq})")

    results = {}
    for sched in ("wave", "continuous"):
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True,
                             scheduler=sched,
                             paged_kv=args.paged and sched == "continuous",
                             num_pages=num_pages)
        srv = ServingEngine(cfg, spec, dcfg, params, dparams, scfg)
        if not args.no_warmup:
            # compile the step/prefill/scatter jits outside the timed
            # region; the longest context exceeds the partial budget, so
            # the refresh/partial mode jits compile too, not just "full"
            for j, ctx in enumerate({min(args.contexts),
                                     max(args.contexts)}):
                prompt, _ = continuation_task(corpus, batch=1,
                                              context_len=ctx, seed=1)
                srv.submit(Request(request_id=f"warm-{j}",
                                   prompt=prompt[0], max_new_tokens=8))
            srv.run()
            srv.stats.clear()
            srv.outputs.clear()
            if scfg.paged_kv:  # count the high-water mark from the timed run
                srv.reset_page_high_water()
        # fresh Request objects so arrival/cancel state doesn't leak
        run_reqs = [(off, Request(request_id=r.request_id, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  eos_id=r.eos_id))
                    for off, r in reqs]
        if sched == "continuous":
            outs, wall, lat = run_continuous(srv, run_reqs)
        else:
            outs, wall, lat = run_wave(srv, run_reqs)
        toks = sum(len(o.tokens) for o in outs)
        p50, p95 = percentiles(lat)
        results[sched] = dict(outs=outs, wall=wall, tput=toks / wall,
                              p50=p50, p95=p95, reqs=run_reqs)
        print(f"{sched:>10}: {len(outs)} requests, {toks} tokens in "
              f"{wall:.1f}s -> {toks / wall:.1f} tok/s, "
              f"latency p50={p50:.1f}s p95={p95:.1f}s")
        if sched == "continuous" and args.paged:
            ps = srv.page_stats()
            print(f"{'':>10}  committed pages high-water: "
                  f"{ps['high_water']}/{ps['capacity']} "
                  f"({ps['high_water'] * ps['block_size']} tokens; "
                  f"resident incl. idle cached: "
                  f"{ps['resident_high_water']}; contiguous layout "
                  f"reserves {ps['contiguous_pages'] * ps['block_size']}), "
                  f"admission page-stalls: "
                  f"{int(srv.stats.get('page_stalls', 0))}")

    if not args.no_check:
        scfg = ServingConfig(batch=args.batch, max_len=max_len,
                             prefill_chunk=64, partial_verification=True)
        check_lossless(cfg, spec, dcfg, params, dparams, scfg,
                       results["continuous"]["reqs"],
                       results["continuous"]["outs"])
        print("losslessness: continuous outputs token-identical to "
              "single-request generation")

    speedup = results["continuous"]["tput"] / max(results["wave"]["tput"],
                                                  1e-9)
    print(f"continuous/wave throughput: {speedup:.2f}x")
    out = ensure_dir(RESULTS_DIR)
    write_rows(f"{out}/bench_serving.csv",
               ["scheduler", "tok_s", "p50_s", "p95_s"],
               [[s, f"{results[s]['tput']:.2f}", f"{results[s]['p50']:.2f}",
                 f"{results[s]['p95']:.2f}"] for s in results])


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
