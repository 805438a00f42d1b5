"""Copy-on-write paged-KV subsystem tests: refcounted pages, prefix
caching, and fork/CoW isolation.

Four layers of invariants:

* ``PageAllocator`` refcounting — fork/attach/cow_write/dec_ref
  lifecycle, double-free detection, high-water immunity to fork (which
  allocates nothing), plus a hypothesis sweep over random
  admit/fork/write/evict sequences asserting no page is ever
  double-owned or leaked (``allocated + free == capacity`` with
  refcounts consistent against a model of every holder).
* ``PrefixCache`` — chained block hashes, longest-prefix match, LRU
  eviction that only reclaims unreferenced entries.
* CoW data isolation — a write through one fork's table never perturbs
  the other holder's view of the shared pages.
* end-to-end sharing — two requests with a long common prefix served
  through the continuous scheduler are token-identical to cold-start
  solo runs while the shared prefix occupies one physical copy, and an
  engine-level fork stays bit-identical under the forker's decoding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SpecPVEngine
from repro.core.draft import init_draft_params
from repro.kvcache.cache import PageAllocator, PrefixCache, gather_page_view
from repro.models import api
from repro.serving import Request
from repro.serving.scheduler import ContinuousScheduler, trim_output

pytestmark = [pytest.mark.paged, pytest.mark.prefix]


# ---------------------------------------------------------------------------
# allocator refcounting
# ---------------------------------------------------------------------------

def test_fork_shares_and_free_keeps_shared_pages():
    al = PageAllocator(8)
    a = al.alloc(0, 3)
    assert al.fork(0, 1) == list(a)
    assert all(al.refcount(p) == 2 for p in a)
    assert al.in_use == 3                      # fork allocates nothing
    assert al.free_slot(0) == []               # still shared -> none freed
    assert all(al.refcount(p) == 1 for p in a)
    assert sorted(al.free_slot(1)) == sorted(a)   # last holder frees
    assert al.free == al.capacity


def test_fork_does_not_skew_high_water():
    al = PageAllocator(8)
    al.alloc(0, 2)
    hw = al.high_water
    al.fork(0, 1)
    al.fork(0, 2)
    assert al.high_water == hw == 2

    al2 = PageAllocator(8)
    al2.alloc(0, 4)
    al2.free_slot(0)
    al2.alloc(1, 2)
    al2.fork(1, 2)                             # 2 refs on 2 pages
    assert al2.high_water == 4 and al2.in_use == 2


def test_cow_write_private_and_shared():
    al = PageAllocator(8)
    a = al.alloc(0, 2)
    # exclusively owned: no copy
    old, new = al.cow_write(0, 1)
    assert old == new == a[1]
    al.fork(0, 1)
    old, new = al.cow_write(1, 1)
    assert old == a[1] and new != old
    assert al.page_at(1, 1) == new and al.page_at(0, 1) == a[1]
    assert al.refcount(a[1]) == 1 and al.refcount(new) == 1
    assert not al.slot_holds_shared(1) or al.refcount(al.page_at(1, 0)) > 1


def test_double_free_and_underflow_detected():
    al = PageAllocator(6)
    a = al.alloc(0, 2)
    al.free_slot(0)
    with pytest.raises(AssertionError, match="double free|underflow"):
        al.dec_ref(list(a))
    b = al.alloc(1, 1)
    al.dec_ref(list(b))
    with pytest.raises(AssertionError):
        al.dec_ref(list(b))                    # page already on free list
    with pytest.raises(AssertionError):
        al.add_ref(list(b))                    # can't ref a free page


def test_attach_orders_blocks_and_counts_refs():
    al = PageAllocator(10)
    shared = al.alloc(99, 2)                   # stand-in "cache owner"
    al.attach(0, shared)
    fresh = al.alloc(0, 2)
    assert al.pages_of(0) == list(shared) + list(fresh)
    assert al.page_at(0, 1) == shared[1] and al.page_at(0, 2) == fresh[0]
    assert al.refcount(shared[0]) == 2 and al.refcount(fresh[0]) == 1


def test_allocator_cow_properties_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    op = st.one_of(
        st.tuples(st.just("alloc"), st.integers(0, 3), st.integers(0, 5)),
        st.tuples(st.just("fork"), st.integers(0, 3), st.integers(0, 3)),
        st.tuples(st.just("cow"), st.integers(0, 3), st.integers(0, 7)),
        st.tuples(st.just("free"), st.integers(0, 3), st.integers(0, 0)),
        st.tuples(st.just("cache_ref"), st.integers(0, 3), st.integers(0, 7)),
        st.tuples(st.just("cache_evict"), st.integers(0, 0),
                  st.integers(0, 0)),
    )

    @settings(max_examples=150, deadline=None)
    @given(st.integers(3, 16), st.lists(op, max_size=60))
    def prop(num_pages, ops):
        al = PageAllocator(num_pages)
        held = {}                              # slot -> list of pages
        cache_refs = []                        # simulated PrefixCache refs
        for kind, slot, arg in ops:
            if kind == "alloc":
                if arg > al.free:
                    with pytest.raises(RuntimeError):
                        al.alloc(slot, arg)
                else:
                    held.setdefault(slot, []).extend(
                        int(p) for p in al.alloc(slot, arg))
            elif kind == "fork":
                dst = arg
                if dst != slot and not held.get(dst):
                    al.fork(slot, dst)
                    held[dst] = list(held.get(slot, []))
            elif kind == "cow":
                pages = held.get(slot, [])
                if pages:
                    blk = arg % len(pages)
                    if al.refcount(pages[blk]) == 1:
                        old, new = al.cow_write(slot, blk)
                        assert old == new
                    elif al.free > 0:
                        old, new = al.cow_write(slot, blk)
                        assert new != old
                        held[slot][blk] = int(new)
            elif kind == "free":
                freed = al.free_slot(slot)
                mine = held.pop(slot, [])
                others = set(cache_refs)
                for pgs in held.values():
                    others.update(pgs)
                # freed exactly the pages nobody else holds
                assert set(freed) == {p for p in mine if p not in others}
            elif kind == "cache_ref":
                owned = sorted({p for pgs in held.values() for p in pgs})
                if owned:
                    p = owned[arg % len(owned)]
                    al.add_ref([p])
                    cache_refs.append(p)
            elif kind == "cache_evict":
                if cache_refs:
                    al.dec_ref([cache_refs.pop()])

            # global invariants: refcount == model's holder count per
            # page; no page leaked or double-owned
            model = {}
            for pgs in list(held.values()) + [cache_refs]:
                for p in pgs:
                    model[p] = model.get(p, 0) + 1
            for p in range(1, num_pages):
                assert al.refcount(p) == model.get(p, 0)
            allocated = sum(1 for p in range(1, num_pages)
                            if al.refcount(p) > 0)
            assert allocated + al.free == al.capacity
            assert al.high_water >= al.in_use == allocated

    prop()


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def _feat(j):
    return np.full((4,), float(j), np.float32)


def test_prefix_cache_match_insert_lru():
    al, dal = PageAllocator(16), PageAllocator(16)
    pc = PrefixCache(block_size=4)
    prompt = np.arange(20, dtype=np.int32)
    pages = al.alloc(0, 3)
    dpages = dal.alloc(0, 3)
    keys = pc.chain_keys(prompt, 3)
    for j in range(3):
        assert pc.insert(keys[j], j, int(pages[j]), int(dpages[j]),
                         _feat(j), al, dal)
    assert not pc.insert(keys[1], 1, 9, 9, _feat(1), al, dal)  # dedupe
    assert all(al.refcount(p) == 2 for p in pages)

    # full-chain match; a diverging prompt matches only the common blocks
    got = pc.match(prompt, 4)
    assert [e.page for e in got] == list(pages)
    div = prompt.copy()
    div[9] += 1                                # breaks block 2 onward
    assert len(pc.match(div, 4)) == 2
    assert len(pc.match(np.arange(100, 120, dtype=np.int32), 4)) == 0

    # slot 0 releases; entries keep the pages resident until LRU eviction
    al.free_slot(0)
    dal.free_slot(0)
    assert al.in_use == 3
    freed = pc.evict_lru(al, dal, 2)
    assert freed == 2 and al.in_use == 1 and len(pc) == 1
    # deepest (least recently chained) blocks went first: block 0 stays
    assert pc.match(prompt, 4)[0].depth == 0


def test_chain_eviction_never_orphans_head():
    """A chain registered under one tick (the engine's pattern) evicts
    deepest-first, so partial eviction shortens the match from the tail
    — it never drops the head and strands unreachable pinned blocks."""
    al, dal = PageAllocator(16), PageAllocator(16)
    pc = PrefixCache(block_size=4)
    prompt = np.arange(12, dtype=np.int32)
    pages, dpages = al.alloc(0, 3), dal.alloc(0, 3)
    tick = pc.new_tick()
    for j, k in enumerate(pc.chain_keys(prompt, 3)):
        pc.insert(k, j, int(pages[j]), int(dpages[j]), _feat(j), al, dal,
                  tick=tick)
    al.free_slot(0)
    dal.free_slot(0)
    assert pc.evict_lru(al, dal, 1) == 1
    assert [e.depth for e in pc.match(prompt, 3)] == [0, 1]
    assert pc.evict_lru(al, dal, 1) == 1
    assert [e.depth for e in pc.match(prompt, 3)] == [0]
    assert al.in_use == 1                      # nothing stranded


def test_prefix_cache_eviction_skips_referenced_pages():
    al, dal = PageAllocator(8), PageAllocator(8)
    pc = PrefixCache(block_size=2)
    prompt = np.arange(6, dtype=np.int32)
    pages, dpages = al.alloc(0, 2), dal.alloc(0, 2)
    for j, k in enumerate(pc.chain_keys(prompt, 2)):
        pc.insert(k, j, int(pages[j]), int(dpages[j]), _feat(j), al, dal)
    # slot 0 still holds the pages -> nothing is evictable
    assert pc.evict_lru(al, dal, 2) == 0 and len(pc) == 2
    al.free_slot(0)
    dal.free_slot(0)
    assert pc.evict_lru(al, dal, 2) == 2 and al.in_use == 0


# ---------------------------------------------------------------------------
# CoW data isolation (pool-level)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 2])
def test_cow_write_never_perturbs_other_holder(layer):
    """On the stored lane-dense pool stack [L, NP, block, Hk*Dh]: a CoW
    copy and a divergent write at every layer, read back at ``layer``."""
    rng = np.random.default_rng(3)
    al = PageAllocator(8)
    blk, hk, dh, n_layers = 4, 2, 8, 3
    pool = jnp.asarray(rng.normal(size=(n_layers, 8, blk, hk * dh))
                       .astype(np.float32))
    pages = al.alloc(0, 2)
    al.fork(0, 1)
    tables = np.stack([al.pages_of(0), al.pages_of(1)]).astype(np.int32)

    def view(slot):
        return np.asarray(gather_page_view(pool[layer], jnp.asarray(tables),
                                           dh)[slot])

    view_before = view(0)
    # slot 1 CoWs block 1 and overwrites it
    old, new = al.cow_write(1, 1)
    assert new != old
    tables[1, 1] = new
    pool = pool.at[:, new].set(pool[:, old])   # engine's device copy
    pool = pool.at[:, new].set(-7.0)           # divergent write
    view_a, view_b = view(0), view(1)
    assert np.array_equal(view_a, view_before)     # slot 0 untouched
    assert np.all(view_b[blk:] == -7.0)            # slot 1 sees its write
    assert al.refcount(pages[0]) == 2              # block 0 still shared


# ---------------------------------------------------------------------------
# engine-level sharing (token identity + single physical copy)
# ---------------------------------------------------------------------------

MAX_LEN = 256
MAX_NEW = 10


@pytest.fixture(scope="module")
def tiny(key, small_dcfg):
    cfg = get_config("tiny-dense")
    params = api.init_params(cfg, key)
    dparams = init_draft_params(cfg, small_dcfg, jax.random.PRNGKey(1))
    return cfg, params, dparams


@pytest.fixture(scope="module")
def solo_contig(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=1, max_len=MAX_LEN, partial_verification=True)


@pytest.fixture(scope="module")
def share_engine(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=2, max_len=MAX_LEN, partial_verification=True,
                        paged=True)                # prefix cache on


def _prompt(cfg, length, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (length,)).astype(np.int32)


def _solo_ref(solo, req):
    toks, _ = solo.generate(req.prompt[None], req.max_new_tokens,
                            eos_id=req.eos_id, prefill_chunk=64)
    row = toks[0]
    return trim_output([int(x) for x in row[row >= 0]],
                       req.max_new_tokens, req.eos_id)


@pytest.mark.slow
@pytest.mark.serving
def test_shared_prefix_token_identity_and_single_copy(tiny, share_engine,
                                                      solo_contig):
    """Two requests sharing a 6-block (96-token) prefix: outputs must be
    token-identical to cold-start solo runs, the second admission must
    hit the prefix cache, and the shared blocks must occupy exactly one
    physical copy (refcounted, not duplicated)."""
    cfg, _, _ = tiny
    bs = share_engine.spec.block_size
    shared = _prompt(cfg, 6 * bs, seed=41)
    tails = [_prompt(cfg, 37, seed=42), _prompt(cfg, 53, seed=43)]
    reqs = [Request(request_id=f"p{i}",
                    prompt=np.concatenate([shared, t]).astype(np.int32),
                    max_new_tokens=MAX_NEW, arrival_s=0.0)
            for i, t in enumerate(tails)]

    sched = ContinuousScheduler(share_engine, prefill_chunk=64)
    for r in reqs:
        sched.submit(r)
    outs = sched.run()
    assert len(outs) == 2 and all(o.finished for o in outs)
    for r in reqs:
        assert np.array_equal(sched.outputs[r.request_id].tokens,
                              _solo_ref(solo_contig, r)), r.request_id

    ps = share_engine.prefix_stats()
    assert ps["blocks_matched"] >= 6           # second admission hit
    assert ps["prefill_tokens_skipped"] >= 6 * bs
    # one physical copy: both slots' leading table entries were the same
    # pages, so the high-water stayed a full prefix short of two cold
    # prompts' worth
    al = share_engine._page_alloc
    cold = sum(share_engine.pages_needed(len(r.prompt), MAX_NEW)
               for r in reqs)
    assert al.high_water == cold - 6
    # the cache still pins the registered blocks — plus each prompt's
    # final-partial-block tail entry — after both slots freed
    ps2 = share_engine.prefix_stats()
    assert ps2["tails"] == 2
    assert al.in_use == len(share_engine._prefix) + ps2["tails"] > 0
    share_engine.reclaim_pages(1 << 30)        # drop idle prefixes + tails
    assert al.in_use == 0 and share_engine._draft_alloc.in_use == 0


@pytest.mark.slow
@pytest.mark.serving
def test_prefix_sharing_lowers_high_water_vs_cold(tiny, small_spec,
                                                 small_dcfg, solo_contig):
    """The same shared-prefix workload served with sharing off must hold
    strictly more resident pages at peak — and outputs stay identical."""
    cfg, params, dparams = tiny
    bs = small_spec.block_size
    shared = _prompt(cfg, 6 * bs, seed=41)
    tails = [_prompt(cfg, 37, seed=42), _prompt(cfg, 53, seed=43)]
    prompts = [np.concatenate([shared, t]).astype(np.int32) for t in tails]

    marks, outputs = {}, {}
    for flag in (True, False):
        eng = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                           batch=2, max_len=MAX_LEN,
                           partial_verification=True, paged=True,
                           prefix_cache=flag)
        sched = ContinuousScheduler(eng, prefill_chunk=64)
        for i, p in enumerate(prompts):
            sched.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=MAX_NEW, arrival_s=0.0))
        sched.run()
        marks[flag] = eng._page_alloc.high_water
        outputs[flag] = [sched.outputs[f"r{i}"].tokens for i in range(2)]
    assert marks[True] <= marks[False] - 6
    for a, b in zip(outputs[True], outputs[False]):
        assert np.array_equal(a, b)


@pytest.mark.slow
def test_fork_slot_cow_isolation(tiny, share_engine):
    """Fork a mid-generation slot, then step only the original: the
    fork's logical view of the (shared) cache must stay bit-identical —
    the original's commits go through copy-on-write, never through a
    still-shared page."""
    cfg, _, _ = tiny
    eng = share_engine
    st = eng.empty_state()
    prompt = _prompt(cfg, 150, seed=77)        # past the partial budget
    st, _ = eng.prefill_into_slot(st, 0, prompt, chunk=64,
                                  max_new_tokens=MAX_NEW)
    # run a couple of steps so fork happens mid-stream (buffer nonempty)
    for _ in range(2):
        groups = eng.select_mode_rows(st, np.array([True, False]))
        for mode, mask in groups.items():
            st, _ = eng.step_rows(st, mode, mask)

    st = eng.fork_slot(st, 0, 1)
    al, dal = eng._page_alloc, eng._draft_alloc
    assert al.pages_of(1) == al.pages_of(0)    # full sharing, no copies

    def views(slot):
        pt = jnp.asarray(np.asarray(st.cache["page_table"])[slot][None])
        dpt = jnp.asarray(np.asarray(st.dcache["page_table"])[slot][None])
        k = np.asarray(jax.vmap(
            lambda pool: gather_page_view(pool, pt))(st.cache["k"]))
        dk = np.asarray(gather_page_view(st.dcache["k"], dpt))
        n = int(np.asarray(st.cache["length"])[slot])
        dn = int(np.asarray(st.dcache["length"])[slot])
        return k[:, 0, :n], dk[0, :dn]

    before_k, before_dk = views(1)
    for _ in range(3):                         # step ONLY the original
        groups = eng.select_mode_rows(st, np.array([True, False]))
        for mode, mask in groups.items():
            st, _ = eng.step_rows(st, mode, mask)
    after_k, after_dk = views(1)
    assert np.array_equal(before_k, after_k)
    assert np.array_equal(before_dk, after_dk)
    # the original diverged onto private pages for its write window
    assert al.pages_of(0) != al.pages_of(1)
    assert not al.slot_holds_shared(0) or any(
        al.refcount(p) > 1 for p in al.pages_of(0))
    st = eng.reset_slot(st, 0)
    st = eng.reset_slot(st, 1)
    # only cached prefixes (chain blocks + whole-prompt tails) stay
    assert al.in_use == len(eng._prefix) + eng.prefix_stats()["tails"]
    eng.reclaim_pages(1 << 30)
    assert al.in_use == 0 and dal.in_use == 0


def test_admission_shortfall_rolls_back_attach(tiny, small_spec, small_dcfg):
    """A request that matches cached prefix blocks but cannot get its
    fresh remainder must raise — with the just-attached references rolled
    back (cache entries intact, slot holding nothing), not crash later
    or leak."""
    cfg, params, dparams = tiny
    eng = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                       batch=1, max_len=MAX_LEN, partial_verification=True,
                       paged=True, num_pages=9)       # 8 usable pages
    st = eng.empty_state()
    al, dal, bs = eng._page_alloc, eng._draft_alloc, small_spec.block_size
    prompt = _prompt(cfg, 150, seed=9)
    # seed the cache with the prompt's first 4 blocks (as if a smaller
    # request had registered them), then leave them idle
    pages, dpages = al.alloc(99, 4), dal.alloc(99, 4)
    for j, k in enumerate(eng._prefix.chain_keys(prompt, 4)):
        eng._prefix.insert(k, j, int(pages[j]), int(dpages[j]),
                           np.zeros(3 * cfg.d_model, np.float32), al, dal)
    al.free_slot(99)
    dal.free_slot(99)
    assert al.idle == 4
    with pytest.raises(RuntimeError, match="fresh pages"):
        # needs ~14 pages, 4 shared -> 10 fresh > 4 free: must roll back
        eng.prefill_into_slot(st, 0, prompt, chunk=64, max_new_tokens=8)
    assert al.count(0) == 0 and dal.count(0) == 0
    assert len(eng._prefix) == 4                     # entries survive
    assert all(al.refcount(p) == 1 for p in pages)   # only the cache ref
    eng.reclaim_pages(1 << 30)
    assert al.in_use == 0 and dal.in_use == 0


@pytest.mark.slow
@pytest.mark.serving
def test_tail_entry_whole_prompt_attach(tiny, small_spec, small_dcfg,
                                        solo_contig):
    """Speculative last-partial-block sharing: a prompt ending in a
    partial block registers a tail entry at prefill finalise; an
    identical later prompt attaches the WHOLE prompt (chain + tail) by
    reference, skips prefill entirely, and still produces bit-identical
    outputs — even though the first request kept decoding (its commits
    write into the very block it registered, which CoW must freeze)."""
    cfg, params, dparams = tiny
    eng = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                       batch=2, max_len=MAX_LEN, partial_verification=True,
                       paged=True)
    bs = small_spec.block_size
    prompt = _prompt(cfg, 9 * bs + 6, seed=31)     # 9 full blocks + 6 tail
    other = _prompt(cfg, 90, seed=32)

    sched = ContinuousScheduler(eng, prefill_chunk=64)
    sched.submit(Request(request_id="cold", prompt=prompt,
                         max_new_tokens=MAX_NEW))
    sched.submit(Request(request_id="other", prompt=other,
                         max_new_tokens=MAX_NEW))
    sched.run()
    ps = eng.prefix_stats()
    assert ps["tails"] == 2 and ps["tail_hits"] == 0

    skipped0 = ps["prefill_tokens_skipped"]
    sched.submit(Request(request_id="warm", prompt=prompt.copy(),
                         max_new_tokens=MAX_NEW))
    sched.run()
    ps = eng.prefix_stats()
    assert ps["tail_hits"] == 1
    # the whole prompt was attached: zero prefill FLOPs for "warm"
    assert ps["prefill_tokens_skipped"] - skipped0 == len(prompt)

    cold = sched.outputs["cold"].tokens
    warm = sched.outputs["warm"].tokens
    assert np.array_equal(cold, warm)
    ref = _solo_ref(solo_contig, Request(request_id="x", prompt=prompt,
                                         max_new_tokens=MAX_NEW))
    assert np.array_equal(warm, ref)
    # admission accounting: every full block is discounted, the tail
    # block stays billed (its attach is a fresh-page copy, so the gate
    # exactly covers _attach_tail_slot's allocation — no deferred debt)
    assert eng.pages_needed_shared(prompt, MAX_NEW) == \
        max(eng.pages_needed(len(prompt), MAX_NEW) - len(prompt) // bs, 0)
    # everything reclaims: no leaked references from attach/register/CoW
    eng.reclaim_pages(1 << 30)
    assert eng._page_alloc.in_use == 0 and eng._draft_alloc.in_use == 0


# ---------------------------------------------------------------------------
# paged decode_full through the Pallas kernel route
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_decode_full_kernel_route_matches(tiny, small_spec, small_dcfg,
                                                monkeypatch):
    """Forcing the paged_verify_attention route (normally TPU-only, here
    interpret mode) must reproduce the gathered-view generation within
    numerical tolerance — same tokens for a short greedy run."""
    from repro.models import dense as dn
    cfg, params, dparams = tiny
    prompt = _prompt(cfg, 90, seed=5)[None]

    eng = SpecPVEngine(cfg, small_spec.replace(use_pallas=True), small_dcfg,
                       params, dparams, batch=1, max_len=MAX_LEN,
                       partial_verification=True, paged=True)
    ref = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                       batch=1, max_len=MAX_LEN,
                       partial_verification=True, paged=True)
    t_ref, _ = ref.generate(prompt, 8, prefill_chunk=64)
    monkeypatch.setattr(dn, "_paged_kernel_ok", lambda: True)
    t_kern, _ = eng.generate(prompt, 8, prefill_chunk=64)
    assert np.array_equal(t_ref, t_kern)
