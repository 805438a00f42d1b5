"""Every per-layer reader named in BENCHMARK.json on a synthetic record:
a number where there is something to read, None where there is not, and
shares of a roofline or a peak within 0-100%."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import flops
from model import dims, load_config
from serve import Tick

BENCH = Path(__file__).resolve().parents[1]
PER_LAYER = json.loads((BENCH.parent / "BENCHMARK.json").read_text())[
    "per_layer"]
ATTN_OUT = ("(f32[8,244,1]{2,1,0}, f32[8,244,1]{2,1,0}, "
            "f32[8,244,128]{2,1,0})")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record(traced=True):
    from repro.configs import SpecPVConfig
    ticks = [Tick(t0=0.1 * i, t1=0.1 * i + 0.09, decode_s=0.08,
                  rows=[("a", 2, 20000, 3), ("b", 1, 15000, 40)],
                  prefill_tokens=512 if i % 4 == 0 else 0)
             for i in range(40)]
    trace = {"window_s": 4.0, "busy_s": 3.6, "idle_s": 0.4,
             "modules": {"jit_run": [40, 3.2],
                         "jit__prefill_chunk_fused": [10, 0.3]},
             "kernels": {"jit_run": {"closed_call.12": [40, 0.9, ATTN_OUT],
                                     "closed_call.3": [5, 0.1, "f32[1,1,8]"]}}}
    return SimpleNamespace(
        dims=dims(load_config("qwen3-8b-L12")), spec=SpecPVConfig(),
        tree_nodes=60, ticks=ticks, trace_host=(0.0, 4.0),
        stats={"mode_rows_partial": 40.0, "mode_rows_refresh": 40.0,
               "mode_rows_full": 0.0, "prefill_tokens": 5120.0},
        prefix={"prefill_tokens_skipped": 15360}, compiles_in_window=0,
        trace=trace if traced else None,
        peaks=flops.peaks("TPU v5 lite") if traced else None)


@pytest.mark.parametrize("m", PER_LAYER, ids=lambda m: m["name"])
def test_reader_reads_the_record(m):
    v = reader(m["name"])(record())
    assert v is not None and v >= 0
    if m["unit"] == "%":
        assert v <= 100.0


def test_shares_are_worked_out_by_hand():
    rec = record()
    assert reader("partial_row_share")(rec) == 50.0
    assert reader("prefill_tick_share")(rec) == 25.0
    assert reader("prefix_hit_share")(rec) == 75.0
    assert reader("idle_share")(rec) == pytest.approx(10.0)
    assert reader("decode_step_ms")(rec) == pytest.approx(80.0)


def test_nothing_to_read_gives_none():
    rec = record()
    rec.trace["kernels"] = {}
    rec.trace["modules"] = {}
    rec.prefix, rec.stats = {}, {}
    for name in ("block_attn_roofline", "decode_step_ms",
                 "prefill_ms_per_ktok", "prefix_hit_share",
                 "partial_row_share"):
        assert reader(name)(rec) is None, name
