"""Paged full-KV cache tests.

Three layers of invariants:

* ``PageAllocator`` — alloc/free round-trips, no double allocation,
  exhaustion raises instead of corrupting state, page 0 reserved
  (deterministic unit tests always run; a hypothesis sweep runs when the
  optional dependency is installed, mirroring test_tree.py).
* token identity — the paged engine's greedy outputs are bit-identical
  to the contiguous engine, batch-1 ``generate`` at context lengths
  straddling the partial budget and through the continuous scheduler,
  including under page-pool memory pressure (admission gated on free
  pages, >slot-count's worth of requests through a sub-contiguous pool).
* ``paged_verify_attention`` — the Pallas scalar-prefetch kernel over the
  physical pool matches dense partials over the gathered logical view.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SpecPVEngine
from repro.core.draft import init_draft_params
from repro.kvcache.cache import PageAllocator, gather_page_view
from repro.models import api
from repro.models import common as cm
from repro.serving import Request
from repro.serving.scheduler import ContinuousScheduler, trim_output

pytestmark = [pytest.mark.paged]


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_alloc_free_roundtrip():
    al = PageAllocator(8)
    assert al.capacity == 7 and al.free == 7 and al.in_use == 0
    a = al.alloc(0, 3)
    b = al.alloc(1, 4)
    assert al.free == 0 and al.in_use == 7 and al.high_water == 7
    assert sorted(list(a) + list(b)) == list(range(1, 8))  # page 0 reserved
    freed = al.free_slot(0)
    assert sorted(freed) == sorted(a) and al.free == 3
    assert al.free_slot(0) == []                           # idempotent
    c = al.alloc(2, 3)
    assert sorted(c) == sorted(a)                          # pages recycled
    assert al.high_water == 7


def test_no_double_allocation():
    al = PageAllocator(10)
    held = []
    for slot in range(3):
        held.extend(al.alloc(slot, 3))
    assert len(set(held)) == len(held) == 9
    assert 0 not in held


def test_exhaustion_raises_and_preserves_state():
    al = PageAllocator(5)
    al.alloc(0, 3)
    before = (al.free, al.in_use, al.pages_of(0))
    with pytest.raises(RuntimeError):
        al.alloc(1, 2)                                     # only 1 free
    assert (al.free, al.in_use, al.pages_of(0)) == before
    assert al.count(1) == 0
    al.alloc(1, 1)                                         # exact fit still ok
    assert al.free == 0


def test_reset_returns_everything():
    al = PageAllocator(6)
    al.alloc(0, 2)
    al.alloc(1, 3)
    al.reset()
    assert al.free == al.capacity == 5
    assert al.count(0) == 0 and al.count(1) == 0


def test_allocator_properties_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=150, deadline=None)
    @given(st.integers(2, 16),
           st.lists(st.tuples(st.integers(0, 3), st.integers(0, 6),
                              st.booleans()), max_size=50))
    def prop(num_pages, ops):
        al = PageAllocator(num_pages)
        held = {}                                          # slot -> set(pages)
        for slot, n, do_free in ops:
            if do_free:
                freed = al.free_slot(slot)
                assert set(freed) == held.pop(slot, set())
            else:
                total_held = sum(len(v) for v in held.values())
                if n > al.capacity - total_held:
                    with pytest.raises(RuntimeError):
                        al.alloc(slot, n)                  # rejects, no corrupt
                else:
                    pages = set(int(p) for p in al.alloc(slot, n))
                    for other in held.values():            # never double-hand
                        assert not (pages & other)
                    assert 0 not in pages
                    held.setdefault(slot, set()).update(pages)
            total_held = sum(len(v) for v in held.values())
            assert al.in_use == total_held
            assert al.free == al.capacity - total_held
            assert al.high_water >= al.in_use

    prop()


# ---------------------------------------------------------------------------
# engine token identity (paged vs contiguous)
# ---------------------------------------------------------------------------

MAX_LEN = 256
MAX_NEW = 12


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
def solo_paged(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    return SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=1, max_len=MAX_LEN, partial_verification=True,
                        paged=True)


@pytest.fixture(scope="module")
def serve_paged(tiny, small_spec, small_dcfg):
    # prefix sharing off: these tests exercise pure paged admission (and
    # swap the trunk allocator wholesale, which a live prefix cache
    # holding references would not survive); the copy-on-write / prefix
    # sharing paths are covered in tests/test_prefix_cow.py
    return SpecPVEngine(*tiny[:1], small_spec, small_dcfg, *tiny[1:],
                        batch=2, max_len=MAX_LEN, partial_verification=True,
                        paged=True, prefix_cache=False)


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
@pytest.mark.parametrize("ctx", [48, 112, 160])
def test_generate_identity_paged_vs_contiguous(tiny, solo_contig, solo_paged,
                                               ctx):
    """Batch-1 greedy generation must be bit-identical across cache
    layouts at lengths below, at, and above the partial budget (112),
    covering the full/refresh/partial mode schedule through the paged
    read, commit, and retrieval paths."""
    cfg, _, _ = tiny
    prompt = _prompt(cfg, ctx, seed=100 + ctx)[None]
    tc, sc = solo_contig.generate(prompt, MAX_NEW, prefill_chunk=64)
    tp, sp = solo_paged.generate(prompt, MAX_NEW, prefill_chunk=64)
    assert np.array_equal(tc, tp)
    assert sc["modes"] == sp["modes"]


@pytest.mark.slow
@pytest.mark.serving
def test_continuous_paged_lossless_under_memory_pressure(tiny, serve_paged,
                                                         solo_contig):
    """Serve 5 mixed-length requests through 2 slots with the allocator
    capped below the contiguous 2 x max_len reservation: admission must
    stall on pages (not corrupt them), every request must finish with
    solo-identical tokens, and the resident-page high-water mark must
    stay under both the cap and the contiguous equivalent."""
    cfg, _, _ = tiny
    nb_seq = serve_paged._nb_seq
    contiguous_pages = serve_paged.batch * nb_seq
    big = serve_paged._page_alloc
    cap = serve_paged.pages_needed(160, MAX_NEW) + 5       # ~1 big + 1 small
    assert cap < contiguous_pages
    serve_paged._page_alloc = PageAllocator(cap + 1)
    try:
        reqs = []
        for i, ctx in enumerate([160, 48, 48, 96, 48]):
            reqs.append(Request(
                request_id=f"r{i}", prompt=_prompt(cfg, ctx, seed=200 + i),
                max_new_tokens=MAX_NEW, arrival_s=0.0))
        sched = ContinuousScheduler(serve_paged, prefill_chunk=64)
        for r in reqs:
            sched.submit(r)
        outs = sched.run()
        assert len(outs) == 5 and all(o.finished for o in outs)
        for r in reqs:
            assert np.array_equal(sched.outputs[r.request_id].tokens,
                                  _solo_ref(solo_contig, r)), r.request_id
        al = serve_paged._page_alloc
        assert sched.stats["page_stalls"] > 0              # pressure was real
        assert al.high_water <= cap < contiguous_pages
        assert al.in_use == 0                              # no page leaks
    finally:
        serve_paged._page_alloc = big


@pytest.mark.slow
@pytest.mark.serving
def test_paged_rejects_oversized_instead_of_corrupting(tiny, serve_paged):
    """A request that can never fit the pool is rejected outright; the
    queue keeps draining."""
    cfg, _, _ = tiny
    big = serve_paged._page_alloc
    serve_paged._page_alloc = PageAllocator(5)             # 4 usable pages
    try:
        sched = ContinuousScheduler(serve_paged, prefill_chunk=64)
        sched.submit(Request(request_id="huge",
                             prompt=_prompt(cfg, 160, seed=300),
                             max_new_tokens=MAX_NEW, arrival_s=0.0))
        sched.tick()
        out = sched.outputs["huge"]
        assert out.finish_reason == "rejected" and not out.finished
        assert serve_paged._page_alloc.in_use == 0
    finally:
        serve_paged._page_alloc = big


# ---------------------------------------------------------------------------
# paged verification-attention kernel (scalar-prefetch index_map reuse)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_verify_attention_matches_gathered_view(use_pallas, layer):
    """The kernel reads layer ``layer`` of the stored lane-dense pool
    stack [L, NP, block, Hk*Dh] in place; the reference gathers that
    layer's logical view."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(0)
    num_pages, bs, hk, dh, b, nb, t, h = 9, 16, 2, 8, 2, 4, 5, 4
    n_layers = 3
    pool_k = jnp.asarray(rng.normal(size=(n_layers, num_pages, bs, hk * dh))
                         .astype(np.float32))
    pool_v = jnp.asarray(rng.normal(size=(n_layers, num_pages, bs, hk * dh))
                         .astype(np.float32))
    pt = jnp.asarray(np.array([[1, 3, 5, 0], [2, 4, 6, 7]], np.int32))
    length = jnp.asarray(np.array([41, 64], np.int32))
    q = jnp.asarray(rng.normal(size=(b, t, h, dh)).astype(np.float32))

    m, l, acc = kops.paged_verify_attention(q, pool_k, pool_v, layer, pt,
                                            length, use_pallas=use_pallas)
    kv_k = gather_page_view(pool_k[layer], pt, dh)
    kv_v = gather_page_view(pool_v[layer], pt, dh)
    valid = jnp.arange(nb * bs)[None] < length[:, None]
    mr, lr, accr = cm.dense_attn_part(q, kv_k, kv_v,
                                      mask=valid[:, None, None, :])
    out = cm.combine_attn_parts([(m, l, acc)], jnp.float32)
    ref = cm.combine_attn_parts([(mr, lr, accr)], jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
