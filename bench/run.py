"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``, whose ``generator`` names a module of
``bench/traffic/``), with the limits of its correctness numbers in
``bench/limits/<cell>.json``.  The configuration's ``reference`` names
its architecture: ``bench/archs/<reference>.py`` (sizes, the program's
configuration, the weights' layout, work counts; the contract is in
``bench/model.py``) and ``bench/refs/<reference>.py`` (the plain
reference ``correct`` compares with).  Per-layer metrics are readers in
``bench/metrics/<metric>.py``.  Everything is found by name.

So a new model comes as new files only, with no edit to a file that is
there: ``configs/<config>.json``; ``archs/<name>.py`` and
``refs/<name>.py`` where no architecture there fits it;
``limits/<cell>.json`` for each cell; a traffic file or a reader where
the cell needs one; and its entries in ``BENCHMARK.json``.

Set-up: weights from the seed on the device, the engine, every decode
and prefill variant of the cell compiled and run once, then the clients
start; the window opens once each client has finished a request.  The
window runs ``--seconds``; with ``--trace 1`` its first
``TRACE_SECONDS`` are traced and the per-layer metrics are reported in
place of the end-to-end ones.  Then the sample of finished requests is
checked against the plain reference (``bench/correct.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
with ``compared`` last: each number checked, with its limit.  The same
numbers close stderr.  With no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import gc                                                # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402
from pathlib import Path                                 # noqa: E402
from types import SimpleNamespace                        # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from model import load_module                            # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"      # fixed, inside the checkout
TRACE_SECONDS = 6.0                  # traced part of a --trace 1 window
DRAIN_SECONDS = 60.0                 # wait for late first tokens / answers


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    return bench, cells[name]


def cell_metrics(bench: dict, cell: dict):
    """(end-to-end, per-layer) metric entries this cell reports."""
    def applies(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in names]
    return e2e, layer


class Compiles:
    """Compile and persistent-cache events from ``jax.monitoring``."""

    def __init__(self, clock):
        import jax
        self.clock = clock
        self.compiles = []            # (host time, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((self.clock(), secs))

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0, t1) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)

    def report(self) -> str:
        return (f"compile cache: {self.hits} hits, {self.misses} misses, "
                f"{len(self.compiles)} backend compiles taking "
                f"{sum(s for _, s in self.compiles):.1f} s")


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}"
                         f" devices; nothing was run")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs


def run(args, *, root: Path = ROOT, chip_check: bool = True,
        clock=time.perf_counter, t_start: float = T_START,
        tamper=None, keep: dict = None) -> dict:
    """One run of a cell; returns the result object.  ``chip_check``
    False and ``tamper`` (a hook that may break the timed path) are for
    the benchmark's own tests; ``keep`` receives the run's record, the
    checked sample, the weights and the reference (``control.py``)."""
    import jax
    bench, cell = load_cell(args.workload, root)
    devs = require_chips(cell["chips"]) if chip_check else jax.devices()
    if chip_check:
        enable_cache()
    comp = Compiles(clock)
    import model as mdl
    import serve
    import correct as cor
    conf = mdl.load_config(cell["config"], root / "bench")
    mix = json.loads((root / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" /
                         f"{cell['name']}.json").read_text())["limits"]
    genmod = load_module(root / "bench" / "traffic" /
                         f"{mix['generator']}.py", f"gen_{mix['generator']}")
    arch = mdl.arch(conf, root / "bench")
    dm = arch.dims(conf)
    gen = genmod.make(mix["params"], args.seed, dm.vocab)
    sv = conf["serving"]

    from weights import make_weights
    params, dparams = make_weights(dm, args.seed)
    jax.block_until_ready((params, dparams))
    engine = serve.build_engine(arch.program_configs(conf), sv, gen, params,
                                dparams)
    if tamper is not None:
        tamper(engine)
    warmed = serve.warm(engine, gen, sv["prefill_chunk"], sv["prefill_budget"])
    log(f"bench: {args.workload} seed {args.seed}: {warmed}, max_len "
        f"{engine.max_len}, {engine.num_pages} pages")

    from repro.serving import ContinuousScheduler
    sched = ContinuousScheduler(engine, prefill_chunk=sv["prefill_chunk"],
                                prefill_budget=sv["prefill_budget"],
                                clock=clock)
    rec = serve.Recorder(engine, sched, clock, spans=bool(args.trace))
    clients = serve.Clients(gen, sched, clock)
    clients.start()
    guard = t_start + 600.0
    while clients.completed_clients() < gen.clients:
        t = rec.tick()
        clients.after_tick(t.t1)
        if clock() > guard:
            raise SystemExit("bench: set-up did not finish a request per "
                             "client in 600 s")
    t0 = clock()
    setup_s = t0 - t_start
    log(f"bench: {comp.report()}; set-up {setup_s:.1f} s")
    stats0 = dict(sched.stats)
    prefix0 = engine.prefix_stats()
    n_ticks0 = len(rec.ticks)
    t1 = t0 + args.seconds
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        traced = jax.profiler.TraceAnnotation("bench.traced")
        traced.__enter__()
        tr0 = clock()
        tr1 = t0 + min(TRACE_SECONDS, args.seconds)
    while clock() < t1:
        t = rec.tick()
        clients.after_tick(t.t1)
        if trace_dir and tr1 is not None and clock() >= tr1:
            traced.__exit__(None, None, None)
            trace_host = (tr0, clock())
            jax.profiler.stop_trace()
            tr1 = None
    if trace_dir and tr1 is not None:
        traced.__exit__(None, None, None)
        trace_host = (tr0, clock())
        jax.profiler.stop_trace()
    stats1 = dict(sched.stats)
    prefix1 = engine.prefix_stats()
    in_window = rec.ticks[n_ticks0:]
    compiles = comp.between(t0, t1)
    # drain: no new requests; wait for the window's first tokens and for
    # enough finished answers to check
    clients.open = False
    t_drain = clock() + DRAIN_SECONDS
    sent = [r for r, ts in clients.submitted.items() if t0 <= ts <= t1]
    while clock() < t_drain and sched.has_work() and any(
            clients.seen[r] == 0 and r not in clients.failed for r in sent):
        t = rec.tick()
        clients.after_tick(t.t1)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[: cell["chips"]])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(peak)}

    import window as win
    e2e_vals = win.end_to_end(clients.deliveries, clients.submitted, t0, t1)
    attempted = len(sent)
    failed = sum(1 for r in sent if r in clients.failed)

    finished = []
    for rid, r in clients.requests.items():
        if rid in clients.done and rid not in clients.failed:
            finished.append({"rid": rid, "prompt": r.prompt,
                             "tokens": clients.tokens_of(rid),
                             "steps": rec.steps.get(rid, [])})
    record = SimpleNamespace(
        dims=dm, conf=conf, mix=mix, spec=engine.spec, cell=cell,
        tree_nodes=engine.tree.size,
        window=(t0, t1), ticks=in_window,
        stats={k: stats1.get(k, 0.0) - stats0.get(k, 0.0)
               for k in set(stats0) | set(stats1)},
        prefix={k: prefix1.get(k, 0) - prefix0.get(k, 0)
                for k in set(prefix0) | set(prefix1)},
        compiles_in_window=compiles, trace=None, peaks=None,
        device_kind=devs[0].device_kind)
    # free the program's state before the reference runs
    del sched, engine, rec
    clients.sched = None
    gc.collect()

    e2e, per_layer = cell_metrics(bench, cell)
    metrics = {}
    result = {}
    if args.trace:
        trc = load_module(root / "bench" / "trace.py", "bench_trace")
        import flops
        record.peaks = flops.peaks(devs[0].device_kind)
        pd = trc.load(trace_dir)
        record.trace = trc.reduce(pd)
        record.trace_host = trace_host
        del pd
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        for m in per_layer:
            reader = load_module(root / "bench" / "metrics" /
                                 f"{m['name']}.py", f"metric_{m['name']}")
            v = reader.read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = record.trace["breakdown"]
    else:
        for m in e2e:
            v = setup_s if m["name"] == "setup_s" else e2e_vals.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"bench: window {args.seconds} s: {e2e_vals}; {attempted} sent, "
        f"{failed} failed, {compiles} compiles in window; stats "
        f"{ {k: v for k, v in sorted(record.stats.items()) if v} }")

    ref = cor.reference(conf["reference"], root / "bench")
    reqs = cor.sample(finished, args.seed)
    t_ref = clock()
    ok, compared, vals = cor.check(ref, dm, params, conf["specpv"], reqs,
                                   limits)
    log(f"bench: reference over {len(reqs)} requests "
        f"({sum(len(r['tokens']) for r in reqs)} tokens) took "
        f"{clock() - t_ref:.1f} s; readings {vals}")
    ok = ok and failed == 0
    if keep is not None:
        keep.update(record=record, reqs=reqs, weights=params, dims=dm,
                    ref=ref, spec=conf["specpv"], compared=compared)
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["compared"] = compared
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args)
    print(json.dumps(out), flush=True)
    for k, c in out["compared"].items():
        log(f"compared {k}: {c['value']!r} limit {c['limit']!r}")
    # the numbers compared close stderr: leave before the runtime's own
    # teardown can log after them
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
