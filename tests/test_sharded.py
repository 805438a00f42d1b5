"""Mesh-parallel sharded serving tests.

Four layers:

* ``PageAllocator`` per-shard pools — contiguous page ranges partition
  the pool, allocation draws only from the owning slot's shard,
  exhaustion is shard-local, high-water marks are tracked per shard
  (``peak_pages_per_host``), and fork/CoW stay shard-local (a
  cross-shard fork would make one host reference pages another holds).
* the all-gather-free verify path — ``cp_full_verify_attention`` equals
  a dense masked reference on a 1-shard mesh AND on a real 8-device
  mesh (the flash softmax-partials merge is exact); the retrieval
  path's shard-local top-k is exact when the global top-k is spread
  evenly across shards and boundedly divergent otherwise; the
  interconnect-traffic model shows the >=10x win at paper scale.
* ``PrefixCache`` persistence — ``save_state``/``load_state`` survive
  an engine rebuild, every re-attached entry re-verifies its chain
  hash first (a corrupted snapshot entry and all its descendants are
  refused), and restored entries serve prefix matches again.
* engine-level sharding (slow) — a mesh-size-1 engine is bit-identical
  to the unsharded fused step; on a forced 8-CPU-device mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the
  data-sharded continuous scheduler is token-identical to the
  single-host baseline while no shard's resident pages exceed its own
  pool range.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SpecPVConfig, get_config
from repro.core import SpecPVEngine
from repro.core.draft import init_draft_params
from repro.distributed import (cp_full_verify_attention,
                               cp_partial_verify_attention,
                               gathered_blocks_bytes, merged_partials_bytes,
                               verify_traffic_report)
from repro.kvcache.cache import PageAllocator, PrefixCache
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serving import Request, ServingConfig, ServingEngine
from repro.serving.scheduler import ContinuousScheduler

pytestmark = pytest.mark.sharded

NDEV = jax.device_count()
needs8 = pytest.mark.skipif(
    NDEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _slot_shard(batch, shards):
    return lambda slot: slot * shards // batch


# ---------------------------------------------------------------------------
# per-shard page pools
# ---------------------------------------------------------------------------

def test_shard_ranges_partition_the_pool():
    al = PageAllocator(33, shards=4, slot_shard=_slot_shard(8, 4))
    assert sum(al.shard_capacity(s) for s in range(4)) == al.capacity == 32
    assert sum(al.free_in(s) for s in range(4)) == al.free
    # every non-null page belongs to exactly one shard, monotonically
    shards_of = [al.page_shard(p) for p in range(1, 33)]
    assert shards_of == sorted(shards_of)
    assert set(shards_of) == {0, 1, 2, 3}


def test_alloc_draws_from_the_slot_shard():
    al = PageAllocator(33, shards=4, slot_shard=_slot_shard(8, 4))
    for slot in range(8):
        pages = al.alloc(slot, 2)
        want = slot * 4 // 8
        assert al.slot_shard(slot) == want
        assert all(al.page_shard(int(p)) == want for p in pages)


def test_exhaustion_is_shard_local():
    al = PageAllocator(9, shards=2, slot_shard=_slot_shard(2, 2))
    al.alloc(0, al.shard_capacity(0))
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        al.alloc(0, 1)
    al.alloc(1, 1)                      # the other shard is unaffected
    assert al.free_in(0) == 0 and al.free_in(1) > 0


def test_per_shard_high_water_and_peak_per_host():
    al = PageAllocator(17, shards=2, slot_shard=_slot_shard(2, 2))
    a = al.alloc(0, 3)
    al.alloc(1, 5)
    assert al.high_water_by == [3, 5]
    assert al.peak_pages_per_host == 5
    al.dec_ref(a)
    assert al.high_water_by == [3, 5]   # high water never recedes
    assert al.high_water == 8           # the global mark still sums


def test_fork_and_cow_stay_shard_local():
    al = PageAllocator(17, shards=2, slot_shard=_slot_shard(4, 2))
    pages = al.alloc(2, 2)              # slots 2,3 -> shard 1
    with pytest.raises(AssertionError, match="cross-shard fork"):
        al.fork(2, 0)                   # slot 0 lives on shard 0
    assert al.fork(2, 3) == list(pages)
    assert all(al.refcount(int(p)) == 2 for p in pages)
    old, new = al.cow_write(3, 0)
    assert old != new                   # shared -> private copy
    assert al.page_shard(new) == 1      # drawn from the slot's shard
    assert al.refcount(int(pages[0])) == 1


def test_alloc_cache_pages_are_idle():
    al = PageAllocator(9, shards=2, slot_shard=_slot_shard(2, 2))
    (p,) = al.alloc_cache(1, 1)
    assert al.page_shard(p) == 1
    assert al.idle == 1 and al.committed == 0
    al.dec_ref([p], cache=True)
    assert al.free == al.capacity


def test_unsharded_allocator_unchanged():
    al = PageAllocator(8)
    assert al.shards == 1
    assert al.slot_shard(123) == 0
    a = al.alloc(0, 3)
    assert al.high_water == 3 and al.peak_pages_per_host == 3
    al.dec_ref(a)
    assert al.free == al.capacity


# ---------------------------------------------------------------------------
# all-gather-free verify (softmax-partials merge)
# ---------------------------------------------------------------------------

def _dense_ref(q, k, v, length):
    """Masked dense GQA attention in fp32 (the exactness oracle)."""
    b, t, h, dh = q.shape
    s, hk = k.shape[1], k.shape[2]
    qg = q.reshape(b, t, hk, h // hk, dh).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bskd->bkgts", qg,
                    k.astype(jnp.float32)) * (dh ** -0.5)
    mask = jnp.arange(s)[None] < length[:, None]
    sc = jnp.where(mask[:, None, None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(b, t, h, dh)


def _qkv(b=2, s=128, hk=2, h=4, dh=16, t=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(ks[0], (b, s, hk, dh))
    v = jax.random.normal(ks[1], (b, s, hk, dh))
    q = jax.random.normal(ks[2], (b, t, h, dh))
    return q, k, v


def test_cp_full_verify_single_shard_matches_dense():
    mesh = make_mesh((1,), ("model",))
    q, k, v = _qkv()
    length = jnp.asarray([100, 128], jnp.int32)
    with jax.set_mesh(mesh):
        out = cp_full_verify_attention(mesh, "model", q, k, v, length)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_ref(q, k, v, length)),
                               rtol=1e-5, atol=1e-5)


@needs8
def test_cp_full_verify_eight_shards_matches_dense():
    """The merge is exact even when shards hold zero valid keys (short
    rows): their ``m = -inf`` partials drop out of the psum."""
    mesh = make_mesh((8,), ("model",))
    q, k, v = _qkv(s=256)
    length = jnp.asarray([20, 256], jnp.int32)   # row 0: 6 empty shards
    with jax.set_mesh(mesh):
        out = cp_full_verify_attention(mesh, "model", q, k, v, length)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_ref(q, k, v, length)),
                               rtol=1e-5, atol=1e-5)


@needs8
def test_cp_retrieval_sharded_topk_divergence():
    """Shard-local top-(budget/shards): exact when the global top-k is
    spread one-per-shard (engineered scores), boundedly divergent on
    random data (the standard distributed-top-k approximation)."""
    spec = SpecPVConfig(block_size=16)
    b, s, hk, dh, h, t = 1, 8 * 64, 2, 16, 4, 2
    nb = s // 16
    q, k, v = _qkv(b=b, s=s, hk=hk, h=h, dh=dh, t=t, seed=3)
    length = jnp.asarray([s], jnp.int32)
    mesh = make_mesh((8,), ("model",))

    # engineered: one standout block per shard -> local == global top-8
    k_eng = k * 0.01
    boosted = [sh * (nb // 8) + 1 for sh in range(8)]
    k_eng = k_eng.at[:, jnp.asarray(
        [bi * 16 + j for bi in boosted for j in range(16)])].mul(300.0)
    for keys, rtol in ((k_eng, 1e-4), (k, None)):
        from repro.kernels import ref
        km, kn = jax.vmap(lambda kk, ll: ref.block_summary_ref(kk, ll, 16))(
            keys, length)
        with jax.set_mesh(mesh):
            out = cp_partial_verify_attention(mesh, "model", spec, 8,
                                              q, keys, v, km, kn, length)
        # global top-8 reference
        sc = jax.vmap(ref.retrieval_score_ref)(q, km, kn, jnp.ones((b, t)))
        nvalid = jnp.clip(length[:, None] - jnp.arange(nb) * 16, 0, 16)
        _, idx = jax.lax.top_k(
            jnp.where((nvalid > 0)[:, None, :], sc, -jnp.inf), 8)
        vlen = jnp.take_along_axis(
            jnp.broadcast_to(nvalid[:, None], (b, hk, nb)), idx, axis=-1)
        m, l, acc = jax.vmap(
            lambda *a: ref.sparse_verify_attention_ref(*a, block_size=16))(
            q, keys, v, idx, vlen)
        from repro.models import common as cm
        out_ref = np.asarray(cm.combine_attn_parts([(m, l, acc)],
                                                   jnp.float32))
        if rtol is not None:
            np.testing.assert_allclose(np.asarray(out), out_ref,
                                       rtol=rtol, atol=1e-4)
        else:
            # bounded divergence: vs the full-attention oracle the
            # shard-local selection must stay within a small factor of
            # the global top-k's own approximation error
            idx_f = jnp.broadcast_to(jnp.arange(nb)[None, None],
                                     (b, hk, nb))
            vlen_f = jnp.broadcast_to(nvalid[:, None], (b, hk, nb))
            m, l, acc = jax.vmap(
                lambda *a: ref.sparse_verify_attention_ref(
                    *a, block_size=16))(q, keys, v, idx_f, vlen_f)
            out_full = np.asarray(cm.combine_attn_parts([(m, l, acc)],
                                                        jnp.float32))
            e_sh = np.linalg.norm(np.asarray(out) - out_full)
            e_gl = np.linalg.norm(out_ref - out_full)
            assert e_sh <= 1.5 * e_gl + 1e-6, \
                f"sharded top-k diverged unboundedly: {e_sh} vs {e_gl}"


def test_traffic_model_ratio_at_paper_scale():
    rep = verify_traffic_report(batch=8, q_tokens=8, num_heads=32,
                                num_kv_heads=8, head_dim=128, num_layers=32,
                                n_shards=8, budget_blocks=128,
                                block_size=128)
    assert rep["traffic_ratio"] >= 10.0
    assert rep["merged_partials_bytes"] > 0
    assert merged_partials_bytes(8, 8, 32, 128, 32, 1) == 0
    assert gathered_blocks_bytes(128, 128, 8, 128, 32, 1) == 0


# ---------------------------------------------------------------------------
# prefix-cache persistence (save/load with chain-hash re-verification)
# ---------------------------------------------------------------------------

def _seed_prefix(pc, al, dal, n_blocks, prompt):
    keys = pc.chain_keys(prompt, n_blocks)
    pages, dpages = al.alloc(0, n_blocks), dal.alloc(0, n_blocks)
    tick = pc.new_tick()
    bs = pc.block
    for j, key in enumerate(keys):
        pc.insert(key, j, int(pages[j]), int(dpages[j]),
                  np.zeros((4,), np.float32), al, dal, tick=tick,
                  tokens=prompt[j * bs:(j + 1) * bs],
                  parent=keys[j - 1] if j > 0 else PrefixCache._ROOT)
    al.free_slot(0)
    dal.free_slot(0)
    return keys


def test_prefix_snapshot_roundtrip():
    bs = 16
    al, dal = PageAllocator(33), PageAllocator(33)
    pc = PrefixCache(block_size=bs)
    prompt = np.arange(5 * bs, dtype=np.int64)
    _seed_prefix(pc, al, dal, 4, prompt)
    snap = pc.save_state(lambda p, dp: {"page": p, "draft_page": dp})
    assert len(snap["entries"]) == 4

    al2, dal2 = PageAllocator(33), PageAllocator(33)
    pc2 = PrefixCache(block_size=bs)
    seated = []

    def seat(d, shard):
        (p,) = al2.alloc_cache(1, shard)
        (dp,) = dal2.alloc_cache(1, shard)
        seated.append(d["pages"]["page"])
        return p, dp

    assert pc2.load_state(snap, al2, dal2, seat) == 4
    assert len(pc2.match(prompt, 4, touch=False, count=False)) == 4
    assert al2.idle == 4                # restored pages are reclaimable


def test_prefix_snapshot_refuses_corrupted_chain():
    """Flipping one block's tokens must refuse that entry AND all its
    descendants (their parent never verified)."""
    bs = 16
    al, dal = PageAllocator(33), PageAllocator(33)
    pc = PrefixCache(block_size=bs)
    prompt = np.arange(5 * bs, dtype=np.int64)
    _seed_prefix(pc, al, dal, 4, prompt)
    snap = pc.save_state(lambda p, dp: {"page": p, "draft_page": dp})
    snap["entries"][1]["tokens"] = snap["entries"][1]["tokens"] + 1

    al2, dal2 = PageAllocator(33), PageAllocator(33)
    pc2 = PrefixCache(block_size=bs)

    def seat(d, shard):
        return al2.alloc_cache(1, shard)[0], dal2.alloc_cache(1, shard)[0]

    assert pc2.load_state(snap, al2, dal2, seat) == 1   # depth-0 only
    assert len(pc2.match(prompt, 4, touch=False, count=False)) == 1


def test_prefix_snapshot_structure_only_restores_nothing():
    bs = 16
    al, dal = PageAllocator(33), PageAllocator(33)
    pc = PrefixCache(block_size=bs)
    _seed_prefix(pc, al, dal, 2, np.arange(3 * bs, dtype=np.int64))
    snap = pc.save_state()              # no page_bytes -> no blobs
    pc2 = PrefixCache(block_size=bs)
    assert pc2.load_state(snap, al, dal,
                          lambda d, s: (_ for _ in ()).throw(
                              RuntimeError("never called"))) == 0


# ---------------------------------------------------------------------------
# slot -> shard mapping matches the batch-axis device sharding
# ---------------------------------------------------------------------------

@needs8
def test_shard_of_slot_matches_named_sharding():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((8, 1), ("data", "model"))
    x = jax.device_put(jnp.arange(8), NamedSharding(mesh, P("data")))
    order = {d.id: i for i, d in enumerate(mesh.devices.flatten())}
    for sh in x.addressable_shards:
        (row,) = np.asarray(sh.data).tolist()
        assert order[sh.device.id] == row * 8 // 8   # shard_of_slot


# ---------------------------------------------------------------------------
# engine-level identity (slow: builds jitted engines)
# ---------------------------------------------------------------------------

MAX_LEN = 256
MAX_NEW = 8


@pytest.fixture(scope="module")
def tiny(key, small_dcfg):
    cfg = get_config("tiny-dense")
    params = api.init_params(cfg, key)
    dparams = init_draft_params(cfg, small_dcfg, jax.random.PRNGKey(1))
    return cfg, params, dparams


def _prompts(cfg, rng, n):
    return [rng.integers(1, cfg.vocab_size - 1, size=ln).astype(np.int32)
            for ln in rng.integers(40, 100, size=n)]


def _serve(eng, prompts):
    sched = ContinuousScheduler(eng, prefill_chunk=64)
    for i, p in enumerate(prompts):
        sched.submit(Request(request_id=f"r{i}", prompt=p,
                             max_new_tokens=MAX_NEW, arrival_s=0.0))
    done = sched.run()
    return {o.request_id: list(o.tokens) for o in done}


@pytest.mark.slow
def test_mesh_size_one_engine_token_identical(tiny, small_spec, small_dcfg):
    cfg, params, dparams = tiny
    mesh = make_mesh((1, 1), ("data", "model"))
    base = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=2, max_len=MAX_LEN,
                        partial_verification=True, paged=True)
    meshed = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                          batch=2, max_len=MAX_LEN,
                          partial_verification=True, paged=True, mesh=mesh)
    assert meshed.data_shards == 1
    prompts = _prompts(cfg, np.random.default_rng(7), 3)
    assert _serve(base, prompts) == _serve(meshed, prompts)


def test_unfillable_mesh_shape_raises(tiny, small_spec, small_dcfg):
    """A mesh_shape the devices cannot fill is an error, never a silent
    single-device run."""
    cfg, params, dparams = tiny
    srv = ServingEngine(cfg, small_spec, small_dcfg, params, dparams,
                        ServingConfig(batch=2, max_len=MAX_LEN, paged_kv=True,
                                      mesh_shape=(NDEV + 1, 1)))
    srv.submit(Request(request_id="r", prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=2))
    with pytest.raises(ValueError, match="mesh_shape"):
        srv.run()


@pytest.mark.slow
@needs8
def test_data_sharded_serving_token_identical(tiny, small_spec, small_dcfg):
    """8-way data sharding: rows are independent, so the sharded
    continuous scheduler must reproduce the single-host tokens exactly
    while every shard's resident pages stay within its own pool range."""
    cfg, params, dparams = tiny
    base = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                        batch=8, max_len=MAX_LEN,
                        partial_verification=True, paged=True)
    mesh = make_mesh((8, 1), ("data", "model"))
    meshed = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                          batch=8, max_len=MAX_LEN,
                          partial_verification=True, paged=True, mesh=mesh)
    assert meshed.data_shards == 8
    prompts = _prompts(cfg, np.random.default_rng(11), 6)
    assert _serve(base, prompts) == _serve(meshed, prompts)
    ps = meshed.page_stats()
    cap = meshed._page_alloc.capacity
    assert ps["peak_pages_per_host"] <= cap // 8 + meshed._nb_seq
    for s in range(8):
        assert (ps[f"high_water_shard_{s}"]
                <= meshed._page_alloc.shard_capacity(s))


@pytest.mark.slow
@needs8
def test_fork_cow_refcounts_under_sharding(tiny, small_spec, small_dcfg):
    """An engine fork on a sharded pool shares pages within the shard
    and CoW isolates the fork — refcounts and free counts balance."""
    cfg, params, dparams = tiny
    mesh = make_mesh((4, 1), ("data", "model"))
    eng = SpecPVEngine(cfg, small_spec, small_dcfg, params, dparams,
                       batch=8, max_len=MAX_LEN,
                       partial_verification=True, paged=True, mesh=mesh)
    assert eng.data_shards == 4
    st = eng.empty_state()
    prompt = np.random.default_rng(5).integers(
        1, cfg.vocab_size - 1, size=70).astype(np.int32)
    # slots 2,3 share shard 1 -> forkable; slot 4 (shard 2) is not
    st, cur = eng.prefill_begin_slot(st, 2, prompt, chunk=64,
                                     max_new_tokens=MAX_NEW)
    while cur.off < len(prompt):
        st, _ = eng.prefill_step_into_slot(st, cur)
    st, _ = eng.prefill_finalize_slot(st, cur)
    al = eng._page_alloc
    free_before = al.free
    shared = [p for p in al.pages_of(2) if p != 0]
    rc_before = [al.refcount(p) for p in shared]   # prefix refs included
    st = eng.fork_slot(st, 2, 3)
    assert al.free == free_before       # fork allocates nothing
    assert [al.refcount(p) for p in shared] == [r + 1 for r in rc_before]
    with pytest.raises(AssertionError, match="cross-shard fork"):
        eng.fork_slot(st, 2, 4)
    st = eng.reset_slot(st, 3)
    assert [al.refcount(p) for p in shared] == rc_before
