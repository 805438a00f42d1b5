"""The trace reduction, on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``make_trace_fixture.py``): three
rounds of ``jit_matmul`` then ``jit_attend`` (the block-attention Pallas
kernel) under ``bench.tick`` spans, with host sleeps between them."""
from pathlib import Path

import pytest

import trace as trc

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    assert DATA.stat().st_size < 2 * 2**20
    return ProfileData.from_file(str(DATA))


@pytest.fixture(scope="module")
def red(pd):
    return trc.reduce(pd)


def test_base_name_strips_ids():
    assert trc.base_name("fusion.123") == "fusion"
    assert trc.base_name("jit_run(7)") == "jit_run"
    assert trc.base_name("jit__prefill_chunk_fused") == \
        "jit__prefill_chunk_fused"


def test_merge_unions_overlaps():
    assert trc._merge([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_op_parts_and_self_time():
    assert trc.op_parts("%fusion.64 = bf16[8]{0} fusion(bf16[8]{0} %a)") == (
        "fusion.64", "bf16[8]{0}")
    assert trc.op_parts("%k.1 = (f32[2,1]{1,0}, f32[2]{0}) custom-call(..)"
                        ) == ("k.1", "(f32[2,1]{1,0}, f32[2]{0})")
    # a (0-10) holds b (1-3) and c (4-6), c holds d; e stands alone
    ops = [("a", 0, 10), ("b", 1, 3), ("c", 4, 6), ("d", 4.5, 5),
           ("e", 12, 13)]
    assert trc.self_times(ops) == [6, 2, 1.5, 0.5, 1]


def test_programs_and_calls(pd, red):
    # the device's clock runs about a millisecond behind the host's in
    # this trace, so the first matmul falls before the traced host span
    mods = red["modules"]
    assert mods["jit_attend"][0] == 3 and mods["jit_matmul"][0] >= 2
    assert all(s > 0 for _, s in mods.values())
    whole = trc.reduce(pd, window=(0.0, 1e18))
    assert whole["modules"]["jit_matmul"][0] == 3


def test_busy_and_idle_add_up(red):
    assert 0 < red["busy_s"] < red["window_s"]
    assert abs(red["busy_s"] + red["idle_s"] - red["window_s"]) < 1e-9
    # the host slept 3 x (2 + 3) ms with the device idle
    assert red["idle_s"] > 0.012


def test_ops_fall_in_their_programs(red):
    by_mod = {}
    for key, secs in red["ops"].items():
        mod = key.split("/", 1)[0]
        by_mod[mod] = by_mod.get(mod, 0.0) + secs
    assert set(by_mod) >= {"jit_matmul", "jit_attend"}
    for mod, secs in by_mod.items():
        assert secs <= red["modules"][mod][1] + 1e-9


def test_block_attention_kernel_is_found(red):
    """The Pallas block-attention call, by its flash-partials result."""
    from readers import BLOCK_ATTN_OUT
    ks = red["kernels"]
    assert set(ks) == {"jit_attend"}
    (calls, secs, out), = ks["jit_attend"].values()
    assert calls == 3 and secs > 0 and BLOCK_ATTN_OUT.match(out)


def test_idle_gaps_by_host_span(red):
    gaps = red["idle_gaps"]
    # 2 ms sleeps inside the tick span, 3 ms sleeps outside any span
    assert gaps.get("bench.tick", 0) > 0.004
    assert gaps.get("host", 0) > 0.006
    assert abs(sum(gaps.values()) - red["idle_s"]) < 1e-6


def test_breakdown_is_sorted_and_short(red):
    bd = red["breakdown"]
    for key in ("device_ops", "idle_gaps"):
        vals = [s for _, s in bd[key]]
        assert 0 < len(vals) <= 10 and vals == sorted(vals, reverse=True)
