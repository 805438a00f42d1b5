"""Host spans of the serving program (``repro.utils.spans``).

A tiny paged, zero-copy, prefix-cached engine under the continuous
scheduler with interleaved prefill, so every span of the tick runs:

* the counters: one ``sched.tick`` per tick, one ``engine.step.wait``
  per decode dispatch, every documented span present;
* nesting: no child's seconds exceed its parent's, and the benchmark's
  ``host_share`` arithmetic lands in 0-100 on them;
* the profiler: under ``jax.profiler.trace`` the host plane holds each
  span, the request-scoped ones carrying ``request_id``;
* no effect on the output: greedy tokens are identical with a profiler
  session and without one.
"""
import glob
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.draft import init_draft_params
from repro.core.engine import SpecPVEngine
from repro.models import api
from repro.serving import ContinuousScheduler, Request

BENCH = Path(__file__).resolve().parents[1] / "bench"

SCHED_SPANS = ("sched.evict", "sched.admit", "sched.prefill", "sched.modes",
               "sched.decode")
STEP_SPANS = ("engine.step.prepare", "engine.step.dispatch",
              "engine.step.wait", "engine.step.post")
PREFILL_SPANS = ("engine.prefill.dispatch", "engine.prefill.wait",
                 "engine.prefill.register")
REQUEST_SPANS = ("sched.admit.request", "engine.prefill.finalize",
                 "engine.prefill.finalize.wait") + PREFILL_SPANS
ALL_SPANS = (("sched.tick", "engine.modes.wait") + SCHED_SPANS
             + STEP_SPANS + REQUEST_SPANS)


@pytest.fixture(scope="module")
def engine(small_spec, small_dcfg):
    cfg = get_config("tiny-dense")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    dparams = init_draft_params(cfg, small_dcfg, jax.random.PRNGKey(1))
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=2, max_len=512, partial_verification=True,
                        paged=True, prefix_cache=True, zero_copy=True)


def _requests(engine):
    """A short prompt (FULL rows) and one past the partial budget
    (REFRESH and PARTIAL rows), two chunks of prefill each at least."""
    rng = np.random.default_rng(3)
    return [Request(request_id=rid, max_new_tokens=10,
                    prompt=rng.integers(0, engine.cfg.vocab_size, (n,))
                    .astype(np.int32))
            for rid, n in (("short", 48), ("long", 160))]


def _serve(engine):
    """Drive ticks until both requests finish; (scheduler, ticks)."""
    sched = ContinuousScheduler(engine, prefill_chunk=32, prefill_budget=32)
    for r in _requests(engine):
        sched.submit(r)
    ticks = 0
    while sched.has_work():
        sched.tick()
        ticks += 1
    return sched, ticks


@pytest.fixture(scope="module")
def served(engine):
    return _serve(engine)


def _secs(stats, name):
    return stats.get("span_s." + name, 0.0)


def test_counters_count_ticks_and_steps(served):
    sched, ticks = served
    st = sched.stats
    assert st["span_n.sched.tick"] == ticks
    assert st["span_n.engine.step.wait"] == st["steps"] > 0
    for name in ALL_SPANS:
        assert st["span_n." + name] > 0, name
        assert st["span_s." + name] >= 0.0, name
    # one decode class counter per tick class, summing to the decodes
    classes = [k for k in st if k.startswith("span_n.sched.decode.")]
    assert classes
    assert sum(st[k] for k in classes) == st["span_n.sched.decode"]
    assert "span_n.sched.decode.refresh" in classes


def test_children_fit_in_parents_and_host_share(served, monkeypatch):
    st = served[0].stats
    tick = _secs(st, "sched.tick")
    assert sum(_secs(st, n) for n in SCHED_SPANS) <= tick
    assert sum(_secs(st, n) for n in STEP_SPANS) <= _secs(st, "sched.decode")
    assert (sum(_secs(st, n) for n in PREFILL_SPANS)
            + _secs(st, "engine.prefill.finalize")
            <= _secs(st, "sched.prefill"))
    assert (_secs(st, "engine.prefill.finalize.wait")
            <= _secs(st, "engine.prefill.finalize"))
    assert _secs(st, "engine.modes.wait") <= _secs(st, "sched.modes")
    assert (sum(_secs(st, f"sched.decode.{c}")
                for c in ("full", "refresh", "partial", "mixed"))
            == pytest.approx(_secs(st, "sched.decode")))
    # the benchmark's reader of these counters
    monkeypatch.syspath_prepend(str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "metric_host_share", BENCH / "metrics" / "host_share.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    share = reader.read(type("Rec", (), {"stats": dict(st)})())
    assert 0.0 <= share <= 100.0


def test_spans_reach_the_profiler_trace(engine, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        sched, _ = _serve(engine)
    xplane, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(dict(e.stats))
    for name in ALL_SPANS:
        assert name in events, name
        # as many trace events as counted calls
        assert len(events[name]) == sched.stats["span_n." + name], name
    for name in REQUEST_SPANS:
        ids = {i for s in events[name] for i in
               str(s.get("request_id", "")).split(",")}
        assert ids <= {"short", "long"} and ids, (name, ids)


def test_tokens_identical_under_the_profiler(engine, served, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        traced, _ = _serve(engine)
    plain = served[0]
    for rid in ("short", "long"):
        assert np.array_equal(traced.outputs[rid].tokens,
                              plain.outputs[rid].tokens), rid
