"""Zero-copy partial KV: page-table-routed partial verification.

The invariants under test:

* ``kernels.ops.routed_partial_attention`` (interpret-mode Pallas on
  CPU) reproduces the ``kernels.ref.sparse_verify_attention_ref``
  oracle on randomized routed pools.
* Greedy serving with ``zero_copy=True`` is token-identical to the
  gathered-partial baseline — plain paged, prefix-shared, tiered, and
  sampled-chain configurations — and drains to zero pinned pages.
* A hypothesis sweep over arbitrary per-row mode vectors: a zero-copy
  fused tick stays ONE jitted dispatch, matches the gathered engine
  row-for-row, and every refresh row's pin set is exactly the physical
  pages its freshly written partial block table routes through.
* Pin refcount accounting through the lifecycle edges: re-refresh
  replaces pins without a transient free, slot eviction mid-window
  drains them, a fork copies them, and a pinned page can neither be
  demoted (``TierManager`` exclusion) nor freed out from under the
  routed reader.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SpecPVEngine
from repro.core.draft import init_draft_params
from repro.core.engine import (MODE_FULL, MODE_PARTIAL, MODE_REFRESH)
from repro.kvcache.cache import PageAllocator
from repro.kvcache.offload import TierManager
from repro.models import api
from repro.serving import Request
from repro.serving.scheduler import ContinuousScheduler

pytestmark = pytest.mark.zero_copy


# ---------------------------------------------------------------------------
# kernel parity (quick-loop friendly)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 2])
def test_routed_attention_matches_ref_oracle(rng, layer):
    """Interpret-mode routed kernel vs the block-sparse reference, on
    layer ``layer`` of a random lane-dense pool stack with ragged valid
    lengths and unused selection slots."""
    from repro.kernels import ops, ref
    b, t, h, hk, dh, npg, bs, ns, nl = 2, 4, 4, 2, 16, 6, 16, 3, 3
    q = jnp.asarray(rng.normal(size=(b, t, h, dh)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(nl, npg, bs, hk * dh)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(nl, npg, bs, hk * dh)),
                         jnp.float32)
    idx = jnp.asarray(rng.integers(0, npg, (b, hk, ns)), jnp.int32)
    vlen = jnp.asarray(rng.integers(0, bs + 1, (b, hk, ns)), jnp.int32)
    m_k, l_k, acc_k = ops.routed_partial_attention(q, pool_k, pool_v, layer,
                                                   idx, vlen,
                                                   use_pallas=True)
    k_flat = pool_k[layer].reshape(npg * bs, hk, dh)
    v_flat = pool_v[layer].reshape(npg * bs, hk, dh)
    m_r, l_r, acc_r = jax.vmap(
        lambda qq, ii, vv: ref.sparse_verify_attention_ref(
            qq, k_flat, v_flat, ii, vv, block_size=bs),
        in_axes=(0, 0, 0))(q, idx, vlen)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# allocator pin accounting + tier exclusion (quick-loop friendly)
# ---------------------------------------------------------------------------

def test_pin_replace_evict_fork_refcounts():
    al = PageAllocator(16)
    pages = al.alloc(0, 6)
    free0 = al.free
    al.pin_slot_pages(0, pages[:3])
    assert sorted(al.pins_of(0)) == sorted(int(p) for p in pages[:3])
    assert al.pinned_pages == 3
    # re-refresh replaces the pin set; the overlap never transiently
    # frees (the new reference lands before the old one is dropped)
    al.pin_slot_pages(0, pages[2:5])
    assert sorted(al.pins_of(0)) == sorted(int(p) for p in pages[2:5])
    assert al.pinned_pages == 3 and al.free == free0
    # a fork copies the pins; either side's eviction leaves the other's
    al.fork(0, 1)
    assert sorted(al.pins_of(1)) == sorted(al.pins_of(0))
    assert al.pinned_pages == 3                # same physical pages
    al.free_slot(0)
    assert al.pins_of(0) == [] and al.pinned_pages == 3
    al.free_slot(1)
    assert al.pinned_pages == 0 and al.free == 15


def test_pinned_page_cannot_free_rebind_or_demote():
    al = PageAllocator(8)
    pages = al.alloc(0, 3)
    al.pin_slot_pages(0, pages[:1])
    p = int(pages[0])
    with pytest.raises(AssertionError):
        al.rebind_block(0, 0, int(pages[1]))
    assert not al.demotable(0, 0) and al.demotable(0, 1)
    with pytest.raises(AssertionError):
        al.demote(0, 0)
    # the pin holds one ref and the slot holds one: releasing both
    # would put a pinned page on the free list -> refused
    al.dec_ref([p])                            # pin's ref still live
    with pytest.raises(AssertionError):
        al.dec_ref([p])


def test_tier_demote_slot_skips_pinned_blocks():
    """TierManager.demote_slot must leave partial-pinned pages seated:
    the routed partial steps between refreshes read them in place."""
    al = PageAllocator(10)
    pages = al.alloc(0, 4)
    tier = TierManager(al, lossless=True)
    l, bs, hk, dh = 1, 4, 1, 2
    cache = dict(
        k=jnp.zeros((l, 10, bs, hk, dh)), v=jnp.zeros((l, 10, bs, hk, dh)),
        kmax=jnp.zeros((l, 10, hk, dh)), kmin=jnp.zeros((l, 10, hk, dh)),
        page_table=jnp.asarray(np.asarray(pages, np.int32)[None]))
    al.pin_slot_pages(0, pages[1:3])
    cache = tier.demote_slot(cache, 0, length=4 * bs)
    hosted = al.hosted_blocks(0)
    assert hosted == [0, 3]                    # pinned blocks 1, 2 stayed
    pt = np.asarray(cache["page_table"])[0]
    assert pt[0] == 0 and pt[3] == 0
    assert pt[1] == pages[1] and pt[2] == pages[2]
    assert al.pinned_pages == 2


# ---------------------------------------------------------------------------
# engine-level token identity + pins through the serving stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny(key, small_dcfg):
    cfg = get_config("tiny-dense")
    params = api.init_params(cfg, key)
    dparams = init_draft_params(cfg, small_dcfg, jax.random.PRNGKey(1))
    return cfg, params, dparams


def _mk_engine(tiny, small_spec, small_dcfg, batch, **kw):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=batch, max_len=512,
                        partial_verification=True, paged=True, **kw)


def _mk_req(cfg, rid, length, max_new, seed, **kw):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, (length,)).astype(np.int32)
    return Request(request_id=rid, prompt=prompt, max_new_tokens=max_new,
                   **kw)


def _run_sched(engine, reqs):
    sched = ContinuousScheduler(engine, prefill_chunk=64, fused=True)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched


def _budget_straddling_reqs(cfg):
    return [_mk_req(cfg, "a", 48, 12, seed=2),
            _mk_req(cfg, "b", 160, 12, seed=3),
            _mk_req(cfg, "c", 96, 12, seed=4),
            _mk_req(cfg, "d", 200, 12, seed=5)]


def test_zero_copy_requires_paged(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    with pytest.raises(AssertionError):
        SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                     batch=2, max_len=512, partial_verification=True,
                     paged=False, zero_copy=True)


@pytest.mark.slow
@pytest.mark.serving
@pytest.mark.paged
def test_zero_copy_token_identity_paged(tiny, small_spec, small_dcfg):
    """Routed refreshes + routed partial reads must reproduce the
    gathered baseline token-for-token, tick for tick — and every pin
    must drain with its slot."""
    cfg, _, _ = tiny
    gat = _mk_engine(tiny, small_spec, small_dcfg, batch=3)
    rtd = _mk_engine(tiny, small_spec, small_dcfg, batch=3, zero_copy=True)
    sg = _run_sched(gat, _budget_straddling_reqs(cfg))
    sr = _run_sched(rtd, _budget_straddling_reqs(cfg))
    for rid in ("a", "b", "c", "d"):
        assert np.array_equal(sg.outputs[rid].tokens,
                              sr.outputs[rid].tokens), rid
    # one dispatch per decode tick, exactly, on the routed engine
    ticks = sum(v for k, v in sr.stats.items()
                if k.startswith("ticks_modes_"))
    assert sr.stats["steps"] == ticks
    assert rtd.page_stats()["pinned_pages"] == 0
    # zero page leaks: drained residency matches the gathered engine's
    # (the prefix cache retains idle cached pages in both, identically)
    assert rtd._page_alloc.in_use == gat._page_alloc.in_use


@pytest.mark.slow
@pytest.mark.serving
@pytest.mark.paged
@pytest.mark.prefix
def test_zero_copy_token_identity_prefix_shared(tiny, small_spec,
                                                small_dcfg):
    """CoW pages in play: a routed refresh may pin pages it shares with
    sibling slots and the prefix cache — identity and drain must hold."""
    cfg, _, _ = tiny
    shared = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (128,)).astype(np.int32)

    def reqs():
        out = []
        for i in range(3):
            tail = np.random.default_rng(20 + i).integers(
                0, cfg.vocab_size, (32 + 16 * i,)).astype(np.int32)
            out.append(Request(request_id=f"s{i}",
                               prompt=np.concatenate([shared, tail]),
                               max_new_tokens=10))
        return out

    gat = _mk_engine(tiny, small_spec, small_dcfg, batch=3)
    rtd = _mk_engine(tiny, small_spec, small_dcfg, batch=3, zero_copy=True)
    sg = _run_sched(gat, reqs())
    sr = _run_sched(rtd, reqs())
    for i in range(3):
        assert np.array_equal(sg.outputs[f"s{i}"].tokens,
                              sr.outputs[f"s{i}"].tokens), i
    assert rtd.prefix_stats()["blocks_matched"] > 0    # sharing was live
    assert rtd.page_stats()["pinned_pages"] == 0


@pytest.mark.slow
@pytest.mark.serving
@pytest.mark.tiered
def test_zero_copy_token_identity_tiered(tiny, small_spec, small_dcfg):
    """Tiered residency under zero-copy: pins land only on DEVICE pages
    (refresh rows promote before dispatch), demotion skips them, and
    outputs stay identical to the gathered tiered engine."""
    cfg, _, _ = tiny
    kw = dict(prefix_cache=False, tiered=True, tier_lossless=True)
    gat = _mk_engine(tiny, small_spec, small_dcfg, batch=2, **kw)
    rtd = _mk_engine(tiny, small_spec, small_dcfg, batch=2,
                     zero_copy=True, **kw)
    # long enough that the pin set (the union of every layer's and kv
    # head's retrieved blocks) cannot cover every committed block, so
    # some blocks are left for the tier to demote
    reqs = [_mk_req(cfg, "a", 320, 16, seed=2),
            _mk_req(cfg, "b", 448, 16, seed=3)]
    sg = _run_sched(gat, list(reqs))
    sr = _run_sched(rtd, [_mk_req(cfg, r.request_id, len(r.prompt), 16,
                                  seed=2 if r.request_id == "a" else 3)
                          for r in reqs])
    for rid in ("a", "b"):
        assert np.array_equal(sg.outputs[rid].tokens,
                              sr.outputs[rid].tokens), rid
    assert rtd.tier_stats()["tier_demoted_pages"] > 0  # tiering was live
    assert rtd.page_stats()["pinned_pages"] == 0


@pytest.mark.slow
@pytest.mark.serving
@pytest.mark.sampling_serving
def test_zero_copy_token_identity_sampled_chain(tiny, small_spec,
                                                small_dcfg):
    """Stochastic chain drafts ride per-slot PRNG streams keyed by the
    request seed, so the routed engine must replay the gathered one's
    sampled tokens exactly."""
    cfg, _, _ = tiny

    def mk(i, n):
        r = _mk_req(cfg, f"r{i}", n, 12, seed=30 + i)
        r.temperature = 0.8
        r.seed = 100 + i
        r.draft = "chain"
        return r

    gat = _mk_engine(tiny, small_spec, small_dcfg, batch=3)
    rtd = _mk_engine(tiny, small_spec, small_dcfg, batch=3, zero_copy=True)
    lens = (48, 160, 96)
    sg = _run_sched(gat, [mk(i, n) for i, n in enumerate(lens)])
    sr = _run_sched(rtd, [mk(i, n) for i, n in enumerate(lens)])
    for i in range(3):
        assert np.array_equal(sg.outputs[f"r{i}"].tokens,
                              sr.outputs[f"r{i}"].tokens), i
    assert rtd.page_stats()["pinned_pages"] == 0


def _expected_pins(eng, st, slot):
    """The physical pages slot's partial block table routes through."""
    al = eng._page_alloc
    pbi = np.asarray(st.pkv_blocks)[slot]
    blocks = np.unique(pbi[pbi >= 0])
    nb = al.count(slot)
    return sorted(al.page_at(slot, int(j)) for j in blocks if j < nb)


@pytest.mark.slow
def test_zero_copy_fused_mode_mix_hypothesis(tiny, small_spec, small_dcfg):
    """For ARBITRARY per-row mode vectors, a zero-copy fused tick stays
    one jitted dispatch, matches the gathered engine row-for-row, and
    every refresh row's pin set is exactly the pages its freshly
    written block table routes through."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st_

    cfg, _, _ = tiny
    engs = {}
    bases = {}
    for name, zc in (("gat", False), ("rtd", True)):
        eng = _mk_engine(tiny, small_spec, small_dcfg, batch=3,
                         zero_copy=zc)
        base = eng.empty_state()
        rng = np.random.default_rng(11)
        for slot, n in enumerate((48, 160, 176)):
            prompt = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            base, _ = eng.prefill_into_slot(base, slot, prompt, chunk=64)
        # one refresh step so partial mode has live routing to read
        base, _ = eng.step_fused(base, np.ones((3,), bool),
                                 eng.modes_for_rows(base,
                                                    np.ones((3,), bool)))
        engs[name], bases[name] = eng, base
    base_active = {n: engs[n]._pkv_active_rows.copy() for n in engs}

    def snapshot(st):
        return jax.tree_util.tree_map(jnp.copy, st)

    @given(modes=st_.lists(st_.sampled_from(
               [MODE_FULL, MODE_REFRESH, MODE_PARTIAL]),
               min_size=3, max_size=3),
           rows=st_.lists(st_.booleans(), min_size=3, max_size=3))
    @settings(max_examples=8, deadline=None)
    def check(modes, rows):
        rows = np.asarray(rows, bool)
        if not rows.any():
            rows = np.array([True, False, False])
        modes = np.asarray(modes, np.int8)
        out = {}
        for name in ("gat", "rtd"):
            eng = engs[name]
            eng._pkv_active_rows[:] = base_active[name]
            before = eng.dispatches
            st, so = eng.step_fused(snapshot(bases[name]), rows, modes)
            assert eng.dispatches == before + 1
            out[name] = (st, so)
        so_g, so_r = out["gat"][1], out["rtd"][1]
        for i in np.nonzero(rows)[0]:
            n = so_g.counts[i]
            assert so_r.counts[i] == n, (i, modes, rows)
            assert np.array_equal(so_r.tokens[i, :n],
                                  so_g.tokens[i, :n]), (i, modes, rows)
        # exact pin accounting on the routed engine
        rtd, (st_r, _) = engs["rtd"], out["rtd"]
        for i in np.nonzero(rows & (modes == MODE_REFRESH))[0]:
            assert sorted(rtd._page_alloc.pins_of(int(i))) == \
                _expected_pins(rtd, st_r, int(i)), (i, modes, rows)

    check()


@pytest.mark.slow
@pytest.mark.paged
def test_zero_copy_pin_lifecycle_evict_fork(tiny, small_spec, small_dcfg):
    """Eviction mid-window drains a slot's pins; a fork copies them, and
    the pinned pages survive the source's eviction for the fork."""
    cfg, _, _ = tiny
    eng = _mk_engine(tiny, small_spec, small_dcfg, batch=3,
                     zero_copy=True, prefix_cache=False)
    al = eng._page_alloc
    st = eng.empty_state()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (160,)).astype(np.int32)
    st, _ = eng.prefill_into_slot(st, 0, prompt, chunk=64)
    rows = np.array([True, False, False])
    st, _ = eng.step_fused(st, rows, eng.modes_for_rows(st, rows))
    pins = sorted(al.pins_of(0))
    assert pins and pins == _expected_pins(eng, st, 0)
    # fork with live pins: the replica holds the same pin set
    st = eng.fork_slot(st, 0, 1)
    assert sorted(al.pins_of(1)) == pins
    # evicting the source mid-window keeps the fork's pages alive
    st = eng.reset_slot(st, 0)
    assert al.pins_of(0) == [] and sorted(al.pins_of(1)) == pins
    assert all(al._ref[p] > 0 for p in pins)
    st = eng.reset_slot(st, 1)
    assert al.pinned_pages == 0 and al.in_use == 0
