"""Tiered KV residency tests (docs/paged_kv.md#residency-tiers).

Four layers of invariants:

* ``PageAllocator`` tier bookkeeping — demote recycles the device page
  and records the promotion debt, promote seats a fresh page and clears
  it, shared pages never demote, free_slot forgives the debt
  (deterministic unit tests plus a hypothesis sweep over
  alloc/demote/promote/free interleavings).
* ``TierManager`` byte round-trips — lossless offload is bit-identical,
  int8 is close with exact kmax/kmin summaries (retrieval scoring is
  unchanged), prefetched segments land free while unprefetched ones pay
  a synchronous promote.
* traffic accounting — ``_record_traffic`` bills full/refresh steps as
  the per-row *sum* of context lengths (regression for the old
  ``nrows x max(len)`` overbilling), refresh adds the partial-cache
  rebuild, and bench_fig4's partial-step token count derives from
  ``SpecPVConfig`` instead of a hardcoded 4576.
* engine/serving identity — greedy generation through a tiered-lossless
  engine is bit-identical to the untiered paged engine (including a
  forced early double-refresh that must fall back to synchronous
  promotion), and tiered admission seats two long-context requests in a
  pool far below their combined untiered working set.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SpecPVConfig, get_config
from repro.core import SpecPVEngine
from repro.core.draft import init_draft_params
from repro.kvcache.cache import PageAllocator
from repro.kvcache.offload import (TierManager, TrafficMeter,
                                   full_step_bytes, partial_step_bytes)
from repro.kvcache.quant import quantize_kv, dequantize_kv
from repro.models import api
from repro.serving import Request
from repro.serving.scheduler import ContinuousScheduler, trim_output

pytestmark = [pytest.mark.tiered]


# ---------------------------------------------------------------------------
# allocator tier bookkeeping
# ---------------------------------------------------------------------------

def test_allocator_demote_promote_roundtrip():
    al = PageAllocator(8)                       # 7 usable pages
    pages = al.alloc(0, 5)
    assert al.free == 2 and al.hosted_count(0) == 0
    for j in (0, 1, 2):
        assert al.demotable(0, j)
        al.demote(0, j)
        assert al.page_at(0, j) == 0            # null-page sentinel
    assert al.free == 5 and al.in_use == 2
    assert al.hosted_count(0) == 3 and al.hosted_blocks(0) == [0, 1, 2]
    assert al.hosted_total == 3 and al.max_hosted() == 3
    seated = [al.promote(0, j) for j in (0, 1, 2)]
    assert al.hosted_count(0) == 0 and al.free == 2 and al.in_use == 5
    assert 0 not in seated and len(set(seated)) == 3
    assert [al.page_at(0, j) for j in (0, 1, 2)] == seated
    assert len(pages) == 5                      # untouched tail still seated


def test_demote_requires_exclusive_ownership():
    al = PageAllocator(8)
    al.alloc(0, 3)
    al.fork(0, 1)                               # refcount 2 on every page
    assert not al.demotable(0, 0) and not al.demotable(1, 0)
    with pytest.raises(AssertionError):
        al.demote(0, 0)
    # breaking the share restores demotability
    al.free_slot(1)
    assert al.demotable(0, 0)


def test_promote_exhaustion_raises_state_unchanged():
    al = PageAllocator(5)                       # 4 usable
    al.alloc(0, 2)
    al.demote(0, 0)
    al.alloc(1, 3)                              # eat the freed page
    before = (al.free, al.in_use, al.hosted_blocks(0))
    with pytest.raises(RuntimeError):
        al.promote(0, 0)                        # no free page to seat it
    assert (al.free, al.in_use, al.hosted_blocks(0)) == before


def test_free_slot_forgives_promotion_debt():
    al = PageAllocator(8)
    al.alloc(0, 4)
    al.demote(0, 1)
    al.demote(0, 2)
    freed = al.free_slot(0)                     # null entries filtered out
    assert len(freed) == 2
    assert al.hosted_count(0) == 0 and al.hosted_total == 0
    assert al.free == al.capacity and al.in_use == 0


def test_allocator_tier_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=120, deadline=None)
    @given(st.integers(4, 16),
           st.lists(st.tuples(st.integers(0, 2), st.integers(0, 4),
                              st.sampled_from(["alloc", "demote", "promote",
                                               "free"])), max_size=40))
    def prop(num_pages, ops):
        al = PageAllocator(num_pages)
        dev = {}                                # slot -> {block: page}
        hosted = {}                             # slot -> set(block)
        for slot, n, op in ops:
            if op == "alloc":
                total = sum(len(v) for v in dev.values())
                if n > al.capacity - total:
                    with pytest.raises(RuntimeError):
                        al.alloc(slot, n)
                else:
                    base = al.count(slot)
                    pages = al.alloc(slot, n)
                    for j, p in enumerate(pages):
                        assert int(p) != 0
                        dev.setdefault(slot, {})[base + j] = int(p)
            elif op == "demote":
                cand = sorted(dev.get(slot, {}))
                if cand:
                    j = cand[n % len(cand)]
                    assert al.demotable(slot, j)
                    al.demote(slot, j)
                    del dev[slot][j]
                    hosted.setdefault(slot, set()).add(j)
            elif op == "promote":
                cand = sorted(hosted.get(slot, ()))
                if cand:
                    j = cand[n % len(cand)]
                    if al.free == 0:
                        with pytest.raises(RuntimeError):
                            al.promote(slot, j)
                    else:
                        p = al.promote(slot, j)
                        assert int(p) != 0
                        for other in dev.values():      # never double-hand
                            assert int(p) not in other.values()
                        dev.setdefault(slot, {})[j] = int(p)
                        hosted[slot].discard(j)
            else:
                freed = al.free_slot(slot)
                assert set(freed) == set(dev.pop(slot, {}).values())
                hosted.pop(slot, None)
            total = sum(len(v) for v in dev.values())
            assert al.in_use == total
            assert al.free == al.capacity - total
            for s in range(3):
                assert al.hosted_count(s) == len(hosted.get(s, ()))
            assert al.hosted_total == sum(len(v) for v in hosted.values())

    prop()


# ---------------------------------------------------------------------------
# TierManager byte round-trips (synthetic pool, no model)
# ---------------------------------------------------------------------------

L, NP, BS, HK, DH = 2, 9, 4, 2, 4


def _mk_pool(seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    shape = (L, NP, BS, HK * DH)            # the stored lane-dense pool
    cache = {
        "k": jnp.asarray(rng.normal(size=shape).astype(dtype)),
        "v": jnp.asarray(rng.normal(size=shape).astype(dtype)),
        "kmax": jnp.asarray(rng.normal(size=(L, NP, HK, DH))
                            .astype(np.float32)),
        "kmin": jnp.asarray(rng.normal(size=(L, NP, HK, DH))
                            .astype(np.float32)),
        "page_table": jnp.zeros((1, NP), jnp.int32),
    }
    return cache


def _seat(cache, al, slot, nblocks):
    pages = al.alloc(slot, nblocks)
    cache = dict(cache)
    cache["page_table"] = cache["page_table"].at[
        slot, jnp.arange(nblocks)].set(jnp.asarray(pages, jnp.int32))
    return cache, [int(p) for p in pages]


@pytest.mark.parametrize("lossless", [True, False])
def test_tier_roundtrip(lossless):
    al = PageAllocator(NP)
    tm = TierManager(al, lossless=lossless, traffic=TrafficMeter())
    cache = _mk_pool(seed=3)
    cache, pages = _seat(cache, al, 0, 5)
    ref = {n: np.asarray(cache[n]) for n in ("k", "v", "kmax", "kmin")}

    cache = tm.demote_slot(cache, 0, length=5 * BS)
    assert al.free == 8 - 5 + 5                 # all 5 recycled
    assert np.all(np.asarray(cache["page_table"])[0, :5] == 0)
    assert tm.demoted_pages == 5 and tm.host_bytes > 0

    cache = tm.promote_slot(cache, 0)
    pt = np.asarray(cache["page_table"])[0, :5]
    assert np.all(pt != 0) and al.hosted_count(0) == 0
    for n in ("kmax", "kmin"):                  # summaries always bit-exact
        np.testing.assert_array_equal(np.asarray(cache[n])[:, pt],
                                      ref[n][:, pages])
    for n in ("k", "v"):
        got, want = np.asarray(cache[n])[:, pt], ref[n][:, pages]
        if lossless:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=0.05)
    assert tm.promoted_pages == 5 and tm.host_bytes == 0
    assert tm.traffic.bytes_by_mode["demote"] \
        == tm.traffic.bytes_by_mode["promote"]


def test_int8_offload_halves_host_bytes():
    peaks = {}
    for lossless in (True, False):
        al = PageAllocator(NP)
        tm = TierManager(al, lossless=lossless)
        cache = _mk_pool(seed=4)
        cache, _ = _seat(cache, al, 0, 4)
        tm.demote_slot(cache, 0, length=4 * BS)
        peaks[lossless] = tm.host_bytes_peak
    # int8 + bf16 scales vs fp32 k/v: exactly half at these shapes (the
    # fp32 kmax/kmin summaries ride along in both)
    assert peaks[False] <= 0.55 * peaks[True]


def test_prefetch_hit_vs_sync_promote():
    al = PageAllocator(NP)
    tm = TierManager(al, lossless=True)
    cache = _mk_pool(seed=5)
    cache, pages0 = _seat(cache, al, 0, 3)
    ref = np.asarray(cache["k"])[:, np.asarray(pages0)]

    cache = tm.demote_slot(cache, 0, length=3 * BS)
    tm.prefetch_slot(0)
    tm.prefetch_slot(0)                         # idempotent
    cache = tm.promote_slot(cache, 0)
    assert tm.prefetch_hits == 1 and tm.sync_promotes == 0

    cache = tm.demote_slot(cache, 0, length=3 * BS)
    cache = tm.promote_slot(cache, 0)           # early refresh: no prefetch
    assert tm.prefetch_hits == 1 and tm.sync_promotes == 1
    pt = np.asarray(cache["page_table"])[0, :3]
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, pt], ref)


def test_drop_slot_clears_host_state():
    al = PageAllocator(NP)
    tm = TierManager(al, lossless=False)
    cache = _mk_pool(seed=6)
    cache, _ = _seat(cache, al, 0, 3)
    cache = tm.demote_slot(cache, 0, length=3 * BS)
    tm.prefetch_slot(0)
    assert tm.host_bytes > 0
    tm.drop_slot(0)
    assert tm.host_bytes == 0
    assert tm.promote_slot(cache, 0) is cache   # nothing left to promote


# ---------------------------------------------------------------------------
# quantization round-trip dtypes
# ---------------------------------------------------------------------------

def test_dequantize_dtype_roundtrip():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 3, 8)).astype(np.float32)
    q, s = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    out32 = dequantize_kv(q, s)                 # default: float32
    assert out32.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out32), x, atol=0.02)
    out16 = dequantize_kv(q, s, dtype=jnp.bfloat16)
    assert out16.dtype == jnp.bfloat16          # requested dtype honoured
    np.testing.assert_allclose(np.asarray(out16, np.float32), x, atol=0.05)


def test_quantize_scale_floor_tiny_bf16():
    # rows of denormal-scale magnitude: the 1e-8 absmax floor must keep
    # the scale finite/nonzero in bf16 and the round-trip NaN-free
    x = jnp.full((2, 4, 8), 1e-9, jnp.bfloat16)
    q, s = quantize_kv(x)
    assert bool(jnp.all(jnp.isfinite(s.astype(jnp.float32))))
    out = dequantize_kv(q, s, dtype=jnp.bfloat16)
    assert not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))


def test_fp8_codec_roundtrip_closeness():
    from repro.kvcache.quant import quantize_kv_fp8
    rng = np.random.default_rng(8)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32) * 4.0
    q, s = quantize_kv_fp8(jnp.asarray(x))
    assert q.dtype == jnp.float8_e4m3fn and s.dtype == jnp.bfloat16
    assert q.nbytes == x.size                   # 1 byte/elem, int8 parity
    out = dequantize_kv(q, s)
    # e4m3 keeps ~3 mantissa bits: elementwise error bounded relative to
    # the row absmax (448-step scale), not the int8 uniform grid
    amax = np.max(np.abs(x), axis=-1, keepdims=True)
    assert np.all(np.abs(np.asarray(out) - x) < 0.08 * amax + 1e-6)
    z = jnp.zeros((2, 3, 8))
    qz, sz = quantize_kv_fp8(z)                 # all-zero rows exact
    np.testing.assert_array_equal(np.asarray(dequantize_kv(qz, sz)),
                                  np.zeros((2, 3, 8), np.float32))


def test_fp8_tier_roundtrip_same_host_bytes():
    """codec="fp8" demote/promote round-trips within fp8 tolerance at
    exactly the int8 codec's host-byte footprint."""
    peaks = {}
    for codec in ("int8", "fp8"):
        al = PageAllocator(NP)
        tm = TierManager(al, codec=codec, traffic=TrafficMeter())
        cache = _mk_pool(seed=9)
        cache, pages = _seat(cache, al, 0, 5)
        ref = {n: np.asarray(cache[n]) for n in ("k", "v")}
        cache = tm.demote_slot(cache, 0, length=5 * BS)
        peaks[codec] = tm.host_bytes_peak
        cache = tm.promote_slot(cache, 0)
        pt = np.asarray(cache["page_table"])[0, :5]
        for n in ("k", "v"):
            np.testing.assert_allclose(np.asarray(cache[n])[:, pt],
                                       ref[n][:, pages], atol=0.2)
        assert tm.host_bytes == 0
    assert peaks["fp8"] == peaks["int8"]        # same bytes on the host


def test_tier_codec_validated():
    with pytest.raises(AssertionError):
        TierManager(PageAllocator(NP), codec="int4")


# ---------------------------------------------------------------------------
# traffic accounting (per-row sums, refresh rebuild, fig4 derivation)
# ---------------------------------------------------------------------------

MAX_LEN = 320
MAX_NEW = 24


@pytest.fixture(scope="module")
def tiny(key, small_dcfg):
    cfg = get_config("tiny-dense")
    params = api.init_params(cfg, key)
    dparams = init_draft_params(cfg, small_dcfg, jax.random.PRNGKey(1))
    return cfg, params, dparams


@pytest.fixture(scope="module")
def solo_ref(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=1, max_len=MAX_LEN, partial_verification=True,
                        paged=True)


@pytest.fixture(scope="module")
def solo_tiered(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=1, max_len=MAX_LEN, partial_verification=True,
                        paged=True, tiered=True, tier_lossless=True)


@pytest.fixture(scope="module")
def serve_tiered(tiny, small_spec, small_dcfg):
    # prefix sharing off: pinned prefix pages are never demotable, and
    # these tests swap the trunk allocator wholesale (see test_paged_kv)
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=2, max_len=MAX_LEN, partial_verification=True,
                        paged=True, prefix_cache=False, tiered=True,
                        tier_lossless=True)


def _bill(eng, mode, seq_len, rows):
    """Run _record_traffic against a fresh meter; return bytes billed."""
    saved, eng.traffic = eng.traffic, TrafficMeter()
    try:
        eng._record_traffic(mode, np.asarray(seq_len, np.int32), rows)
        return eng.traffic.bytes_by_mode.get(mode, 0)
    finally:
        eng.traffic = saved


def test_record_traffic_sums_per_row_lengths(serve_tiered, tiny, small_spec):
    """Regression for the fused-step overbilling: a 2-row step at
    heterogeneous lengths (L, 4L) must bill the analytic per-row sum,
    not ``nrows x max(len)``."""
    from repro.models.dense import attn_layer_count
    cfg, _, _ = tiny
    eng = serve_tiered
    l_attn = attn_layer_count(cfg.layer_kinds())
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    seq = [40, 160]                             # L and 4L
    rows = np.array([True, True])
    got = _bill(eng, "full", seq, rows)
    want = full_step_bytes(l_attn, 1, 200, hk, dh, itemsize)
    overbilled = full_step_bytes(l_attn, 2, 160, hk, dh, itemsize)
    assert got == want and got < overbilled
    # single-row masks bill only their own row
    assert _bill(eng, "full", seq, np.array([True, False])) \
        == full_step_bytes(l_attn, 1, 40, hk, dh, itemsize)
    # rows=None is the lock-step whole-batch path
    assert _bill(eng, "full", seq, None) == want


def test_record_traffic_refresh_bills_rebuild(serve_tiered, tiny, small_spec):
    from repro.models.dense import attn_layer_count
    cfg, _, _ = tiny
    eng = serve_tiered
    l_attn = attn_layer_count(cfg.layer_kinds())
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    rows = np.array([True, True])
    got = _bill(eng, "refresh", [40, 160], rows)
    want = full_step_bytes(l_attn, 1, 200, hk, dh, itemsize) \
        + partial_step_bytes(l_attn, 2, small_spec.partial_budget_tokens,
                             hk, dh, itemsize)
    assert got == want


@pytest.mark.zero_copy
def test_record_traffic_refresh_routed_contract(tiny, small_spec,
                                                small_dcfg):
    """Billing contract under zero-copy: a routed refresh bills the full
    verify read plus page summaries + index writes + tail-buffer bytes
    (``routed_refresh_bytes``) — NOT the gathered body copy — and the
    rebuild term no longer scales with ``partial_budget_tokens``.
    Partial-step billing is unchanged: the body is still read every
    partial step, just routed from the trunk pool."""
    from repro.kvcache.offload import routed_refresh_bytes
    from repro.models.dense import attn_layer_count
    cfg, params, dparams = tiny
    eng = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                       batch=2, max_len=MAX_LEN, partial_verification=True,
                       paged=True, prefix_cache=False, zero_copy=True)
    l_attn = attn_layer_count(cfg.layer_kinds())
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    rows = np.array([True, True])
    got = _bill(eng, "refresh", [40, 160], rows)
    want = full_step_bytes(l_attn, 1, 200, hk, dh, itemsize) \
        + routed_refresh_bytes(l_attn, 2, eng._nb_seq, eng._ns_blocks,
                               small_spec.buffer_size, hk, dh, itemsize)
    assert got == want
    gathered = full_step_bytes(l_attn, 1, 200, hk, dh, itemsize) \
        + partial_step_bytes(l_attn, 2, small_spec.partial_budget_tokens,
                             hk, dh, itemsize)
    assert got != gathered
    # single-row refresh scales the rebuild term by nrows
    got1 = _bill(eng, "refresh", [40, 160], np.array([True, False]))
    assert got1 == full_step_bytes(l_attn, 1, 40, hk, dh, itemsize) \
        + routed_refresh_bytes(l_attn, 1, eng._nb_seq, eng._ns_blocks,
                               small_spec.buffer_size, hk, dh, itemsize)
    # the per-step partial read is billed identically to the gathered
    # engine: budget + buffer tokens of K+V per stepping row
    assert _bill(eng, "partial", [40, 160], rows) == partial_step_bytes(
        l_attn, 2,
        small_spec.partial_budget_tokens + small_spec.buffer_size,
        hk, dh, itemsize)


def test_fig4_partial_tokens_derive_from_config():
    """bench_fig4's projected partial-step size is the SpecPV default
    budget + buffer (4480 + 96), not a hardcoded 4576."""
    spec = SpecPVConfig()
    assert spec.partial_budget_tokens + spec.buffer_size == 4576
    tm = TrafficMeter()
    tm.record("full", 50_000_000_000)
    assert tm.modelled_time_s(25.0) == pytest.approx(2.0)   # GB/s, not Gbit


# ---------------------------------------------------------------------------
# engine / serving identity (tiered lossless vs untiered paged)
# ---------------------------------------------------------------------------

def _prompt(cfg, length, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (length,)).astype(np.int32)


@pytest.mark.slow
@pytest.mark.parametrize("ctx", [48, 160])
def test_generate_identity_tiered_vs_paged(tiny, solo_ref, solo_tiered, ctx):
    """Greedy generation with tier_lossless=True is bit-identical to the
    untiered paged engine below and above the partial budget (112); the
    long context must actually demote."""
    cfg, _, _ = tiny
    prompt = _prompt(cfg, ctx, seed=400 + ctx)[None]
    d0 = solo_tiered.tier_stats()["tier_demoted_pages"]
    tr, sr = solo_ref.generate(prompt, MAX_NEW, prefill_chunk=64)
    tt, st = solo_tiered.generate(prompt, MAX_NEW, prefill_chunk=64)
    assert np.array_equal(tr, tt)
    assert sr["modes"] == st["modes"]
    demoted = solo_tiered.tier_stats()["tier_demoted_pages"] - d0
    assert (demoted > 0) == (ctx > 112)


@pytest.mark.slow
def test_full_tier_cycle_with_prefetch(tiny, solo_ref, solo_tiered):
    """A run long enough for two refreshes exercises the whole cycle:
    demote after refresh #1, prefetch one transition ahead, promote at
    refresh #2 as a prefetch hit — still token-identical."""
    cfg, _, _ = tiny
    prompt = _prompt(cfg, 160, seed=500)[None]
    before = solo_tiered.tier_stats()
    tr, _ = solo_ref.generate(prompt, 80, prefill_chunk=64)
    tt, _ = solo_tiered.generate(prompt, 80, prefill_chunk=64)
    assert np.array_equal(tr, tt)
    after = solo_tiered.tier_stats()
    assert after["tier_promoted_pages"] > before["tier_promoted_pages"]
    assert after["tier_prefetch_hits"] > before["tier_prefetch_hits"]
    assert after["tier_sync_promotes"] == before["tier_sync_promotes"]


@pytest.mark.slow
def test_early_refresh_sync_promote_fallback(tiny, solo_ref, solo_tiered):
    """A refresh forced right after a demotion (no partial step ever ran,
    so no prefetch was issued) must promote synchronously — and stay
    token-identical to the untiered engine on the same forced schedule."""
    cfg, _, _ = tiny
    prompt = _prompt(cfg, 160, seed=600)[None]
    before = solo_tiered.tier_stats()
    st_r = solo_ref.prefill(prompt, chunk=64)
    st_t = solo_tiered.prefill(prompt, chunk=64)
    for mode in ("refresh", "refresh", "partial", "refresh"):
        st_r, out_r = solo_ref.step(st_r, mode)
        st_t, out_t = solo_tiered.step(st_t, mode)
        np.testing.assert_array_equal(out_r.tokens, out_t.tokens)
        np.testing.assert_array_equal(out_r.counts, out_t.counts)
    after = solo_tiered.tier_stats()
    assert after["tier_sync_promotes"] > before["tier_sync_promotes"]


@pytest.mark.slow
def test_generate_single_token_stats_finite(tiny, solo_tiered):
    """max_new_tokens=1 is satisfied by the prefill seed token and never
    enters the step loop: stats must come back finite, not NaN (and no
    empty-mean RuntimeWarning)."""
    cfg, _, _ = tiny
    prompt = _prompt(cfg, 48, seed=700)[None]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        toks, stats = solo_tiered.generate(prompt, 1, prefill_chunk=64)
    assert toks.shape == (1, 1) and toks[0, 0] >= 0
    assert stats["mean_accept"] == 0.0 and stats["steps"] == 0


def _solo_out(solo, req):
    toks, _ = solo.generate(req.prompt[None], req.max_new_tokens,
                            eos_id=req.eos_id, prefill_chunk=64)
    row = toks[0]
    return trim_output([int(x) for x in row[row >= 0]],
                       req.max_new_tokens, req.eos_id)


@pytest.mark.slow
@pytest.mark.serving
def test_tiered_admission_under_memory_pressure(tiny, serve_tiered, solo_ref):
    """Two long-context requests through a pool far below their combined
    untiered working set: the second stalls until the first's
    refresh-demotion returns its cold pages, then both run concurrently
    — lossless, no leaks, and the admission margin never wedges."""
    cfg, _, _ = tiny
    eng = serve_tiered
    need = eng.pages_needed(160, MAX_NEW)
    cold = 160 // eng.spec.block_size
    cap = need + (need - cold) + 3              # 1 full + 1 hot-only slot
    assert cap < 2 * need                       # pressure is real
    big_al, big_tier_al = eng._page_alloc, eng._tier.alloc
    eng._page_alloc = eng._tier.alloc = PageAllocator(cap + 1)
    try:
        reqs = [Request(request_id=f"t{i}",
                        prompt=_prompt(cfg, 160, seed=800 + i),
                        max_new_tokens=MAX_NEW, arrival_s=0.0)
                for i in range(2)]
        sched = ContinuousScheduler(eng, prefill_chunk=64)
        for r in reqs:
            sched.submit(r)
        outs = sched.run()
        assert len(outs) == 2 and all(o.finished for o in outs)
        for r in reqs:
            assert np.array_equal(sched.outputs[r.request_id].tokens,
                                  _solo_out(solo_ref, r)), r.request_id
        al = eng._page_alloc
        assert sched.stats["page_stalls"] > 0   # second request waited
        assert sched.stats["peak_active"] == 2  # ... then ran concurrently
        assert eng.tier_stats()["tier_demoted_pages"] > 0
        assert al.high_water <= cap and al.in_use == 0
        assert al.hosted_total == 0             # debts all repaid/forgiven
    finally:
        eng._page_alloc, eng._tier.alloc = big_al, big_tier_al


def test_tier_ready_rows_force_semantics(serve_tiered):
    """When every active row would defer, ``force=True`` steps the
    smallest debt anyway (the no-other-progress escape hatch) while
    ``force=False`` defers them all — the scheduler's choice while a
    chunked-prefill cursor is still pumping, since the cursor's
    completion (first refresh-demotion) is what returns pages.
    Regression for the pool-exhaustion raise a forced promote hit while
    an open cursor legitimately held the whole free pool."""
    from repro.core.engine import MODE_PARTIAL, MODE_REFRESH
    eng = serve_tiered
    saved = eng._page_alloc
    al = PageAllocator(8)                       # 7 usable
    eng._page_alloc = al
    try:
        al.alloc(0, 4)
        for j in range(4):                      # slot 0 owes 4 pages...
            al.demote(0, j)
        al.alloc(1, al.free)                    # ...and nothing is free
        assert al.free == 0 and al.hosted_count(0) == 4
        rows = np.array([True, False])
        modes = np.array([MODE_REFRESH, MODE_PARTIAL], np.int8)
        kept, deferred = eng.tier_ready_rows(rows, modes, force=False)
        assert not kept.any() and deferred == 1
        kept, deferred = eng.tier_ready_rows(rows, modes, force=True)
        assert kept[0] and deferred == 0        # min-debt row forced
        # a partial row never defers and never spends budget
        kept, deferred = eng.tier_ready_rows(
            np.array([False, True]),
            np.array([MODE_PARTIAL, MODE_PARTIAL], np.int8), force=False)
        assert kept[1] and deferred == 0
    finally:
        eng._page_alloc = saved


@pytest.mark.slow
@pytest.mark.serving
def test_tiered_interleaved_prefill_under_pressure(tiny, serve_tiered,
                                                  solo_ref):
    """The memory-pressure scenario with chunked-prefill interleaving:
    an open cursor seats its whole worst-case page bill up front
    (prefill_begin_slot), so debt-holding refresh rows may find the pool
    legitimately empty for the entire pump window.  They must defer —
    not force a promote into an exhausted pool — and everything still
    completes lossless with zero leaks."""
    cfg, _, _ = tiny
    eng = serve_tiered
    need = eng.pages_needed(160, MAX_NEW)
    cold = 160 // eng.spec.block_size
    cap = need + (need - cold) + 3
    big_al, big_tier_al = eng._page_alloc, eng._tier.alloc
    eng._page_alloc = eng._tier.alloc = PageAllocator(cap + 1)
    try:
        reqs = [Request(request_id=f"i{i}",
                        prompt=_prompt(cfg, 160, seed=900 + i),
                        max_new_tokens=MAX_NEW, arrival_s=0.0)
                for i in range(2)]
        sched = ContinuousScheduler(eng, prefill_chunk=64,
                                    prefill_budget=64)
        for r in reqs:
            sched.submit(r)
        outs = sched.run()
        assert len(outs) == 2 and all(o.finished for o in outs)
        for r in reqs:
            assert np.array_equal(sched.outputs[r.request_id].tokens,
                                  _solo_out(solo_ref, r)), r.request_id
        al = eng._page_alloc
        assert sched.stats["prefill_tokens"] > 0    # interleaving ran
        assert eng.tier_stats()["tier_demoted_pages"] > 0
        assert al.high_water <= cap and al.in_use == 0
        assert al.hosted_total == 0
    finally:
        eng._page_alloc, eng._tier.alloc = big_al, big_tier_al
