"""The readers of the program's own spans and named kernels
(``named.py``; ``partial_attn_roofline``, ``full_attn_roofline``,
``prefill_attn_share``, ``host_share``, ``step_host_ms``): worked out by
hand on a synthetic record, None where there is nothing to read, and the
named kernels as a chip trace shows them (``data/program.xplane.pb``,
made by ``make_program_fixture.py``)."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

import flops
import trace as trc
from model import dims, load_config
from readers import BLOCK_ATTN_OUT, DECODE_MODULES, kernel_seconds
from serve import Tick

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data" / "program.xplane.pb"
ATTN_OUT = ("(f32[8,244,1]{2,1,0}, f32[8,244,1]{2,1,0}, "
            "f32[8,244,128]{2,1,0})")
NEW = ("partial_attn_roofline", "full_attn_roofline", "prefill_attn_share",
       "host_share", "step_host_ms")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record():
    """40 traced decode ticks of one PARTIAL row (20,000 tokens of
    context) and one REFRESH row (15,000, 40 pending), a prefill chunk
    every fourth tick, and the counters of 40 decode steps."""
    from repro.configs import SpecPVConfig
    ticks = [Tick(t0=0.1 * i, t1=0.1 * i + 0.09, decode_s=0.08,
                  rows=[("a", 2, 20000, 3), ("b", 1, 15000, 40)],
                  prefill_tokens=512 if i % 4 == 0 else 0)
             for i in range(40)]
    trace = {"window_s": 4.0, "busy_s": 3.6, "idle_s": 0.4,
             "modules": {"jit_run": [40, 3.2],
                         "jit__prefill_chunk_fused": [10, 0.3]},
             "kernels": {
                 "jit_run": {"specpv_attn_partial.7": [40, 0.6, ATTN_OUT],
                             "specpv_attn_full.7": [40, 0.3, ATTN_OUT],
                             "specpv_retrieval_score.1":
                                 [5, 0.1, "f32[1,1,8]"]},
                 "jit__prefill_chunk_fused": {
                     "specpv_attn_prefill.7": [10, 0.12, ATTN_OUT]}}}
    stats = {"steps": 40.0,
             "span_s.sched.tick": 4.0, "span_n.sched.tick": 40.0,
             "span_s.engine.step.wait": 3.0, "span_n.engine.step.wait": 40.0,
             "span_s.engine.prefill.wait": 0.4,
             "span_s.engine.modes.wait": 0.1,
             "span_s.engine.step.prepare": 0.04,
             "span_s.engine.step.dispatch": 0.08,
             "span_s.engine.step.post": 0.04}
    return SimpleNamespace(
        dims=dims(load_config("qwen3-8b-L12")), spec=SpecPVConfig(),
        tree_nodes=60, ticks=ticks, trace_host=(0.0, 4.0), stats=stats,
        prefix={}, compiles_in_window=0, trace=trace,
        peaks=flops.peaks("TPU v5 lite"))


def attention_least_s(rec, t_q, s_kv):
    """Least time of one row's attention through the 12 layers, by hand:
    4 t s H Dh operations; K and V of s keys per kv head plus the queries
    and outputs, in bf16."""
    fl = 12 * 4.0 * t_q * s_kv * 32 * 128
    by = 12 * (2.0 * s_kv * 8 * 128 * 2 + 2.0 * t_q * 32 * 128 * 2)
    pk = rec.peaks
    return max(fl / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])


def test_readers_worked_out_by_hand():
    rec = record()
    # PARTIAL: root + 60 tree nodes over the 35-block budget, 40 ticks
    partial = 40 * attention_least_s(rec, 61, 35 * 128)
    assert reader("partial_attn_roofline")(rec) == pytest.approx(
        100 * partial / 0.6)
    # REFRESH: 40 pending + 60 nodes over the whole 15,000 tokens
    full = 40 * attention_least_s(rec, 100, 15000)
    assert reader("full_attn_roofline")(rec) == pytest.approx(
        100 * full / 0.3)
    assert reader("prefill_attn_share")(rec) == pytest.approx(40.0)
    # (4.0 - 3.0 - 0.4 - 0.1) of 4.0 s of ticks
    assert reader("host_share")(rec) == pytest.approx(12.5)
    # 0.16 s of prepare + dispatch + post over 40 steps
    assert reader("step_host_ms")(rec) == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    empty = record()
    empty.stats = {}
    empty.trace["kernels"] = {}
    assert reader(name)(empty) is None
    # a program without the names: its kernels as such a trace shows them
    unnamed = record()
    unnamed.stats = {"steps": 40.0}
    unnamed.trace["kernels"] = {"jit_run": {"closed_call.12":
                                            [40, 0.9, ATTN_OUT]}}
    assert reader(name)(unnamed) is None


def test_no_rows_of_the_kernels_modes_gives_none():
    rec = record()
    for t in rec.ticks:
        t.rows = [r for r in t.rows if r[1] != 2]
    assert reader("partial_attn_roofline")(rec) is None
    assert reader("full_attn_roofline")(rec) is not None


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    assert DATA.stat().st_size < 2 * 2**20
    return trc.reduce(ProfileData.from_file(str(DATA)))


def test_kernels_keyed_by_their_names(red):
    names = {mod: {trc.base_name(op) for op in ks}
             for mod, ks in red["kernels"].items()}
    assert names["jit_run"] == {"specpv_attn_full", "specpv_attn_partial",
                                "specpv_retrieval_score"}
    assert names["jit__prefill_chunk_fused"] == {"specpv_attn_prefill"}
    assert names["jit_block_summaries"] == {"specpv_block_summary"}


def test_named_attention_is_what_block_attention_reads(red):
    """The partial and full kernels are the decode step's block-attention
    calls that ``block_attn_roofline`` finds by their result type."""
    import named
    rec = SimpleNamespace(trace=red)
    part = named.named_kernel_s(rec, DECODE_MODULES, named.ATTN_PARTIAL)
    full = named.named_kernel_s(rec, DECODE_MODULES, named.ATTN_FULL)
    assert part > 0 and full > 0
    assert part + full == pytest.approx(
        kernel_seconds(rec, DECODE_MODULES, BLOCK_ATTN_OUT), rel=1e-9)
