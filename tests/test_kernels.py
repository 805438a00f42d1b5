"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp ref oracles
across shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


SHAPES = [  # (S, Hk, Dh, block, H, T)
    (128, 1, 32, 16, 2, 4),
    (256, 2, 64, 32, 4, 8),
    (512, 4, 64, 64, 8, 5),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_summary(shape, dtype):
    s, hk, dh, bs, h, t = shape
    b = 2
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh), dtype)
    length = jnp.asarray([s - bs // 2, s // 2], jnp.int32)
    km, kn = ops.block_summaries(k, length, bs)
    km0, kn0 = jax.vmap(lambda kk, ll: ref.block_summary_ref(kk, ll, bs))(
        k, length)
    np.testing.assert_allclose(np.asarray(km), np.asarray(km0), atol=1e-6)
    np.testing.assert_allclose(np.asarray(kn), np.asarray(kn0), atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_retrieval_score(shape, dtype):
    s, hk, dh, bs, h, t = shape
    b = 2
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh), dtype)
    length = jnp.asarray([s, s // 2], jnp.int32)
    km, kn = ops.block_summaries(k, length, bs)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, dh), dtype)
    qw = (jax.random.uniform(jax.random.PRNGKey(2), (b, t)) > 0.3
          ).astype(jnp.float32)
    qw = qw.at[:, 0].set(1.0)  # at least one query
    sc = ops.retrieval_scores(q, km, kn, qw)
    sc0 = jax.vmap(ref.retrieval_score_ref)(q, km, kn, qw)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc0),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("nsel", [1, 4])
def test_sparse_verify_attention(shape, dtype, nsel):
    s, hk, dh, bs, h, t = shape
    b = 2
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh), dtype)
    v = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, dh), dtype)
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh), dtype)
    nb = s // bs
    idx = jax.random.randint(jax.random.PRNGKey(3), (b, hk, nsel), 0, nb)
    vlen = jax.random.randint(jax.random.PRNGKey(4), (b, hk, nsel), 1,
                              bs + 1)
    m, l, acc = ops.sparse_verify_attention(q, k, v, idx, vlen, bs)
    m0, l0, a0 = jax.vmap(
        lambda *a: ref.sparse_verify_attention_ref(*a, block_size=bs))(
        q, k, v, idx, vlen)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(m), np.asarray(m0), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l0), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(a0), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("shape", [(64, 1, 8, 16), (128, 2, 16, 32),
                                   (96, 3, 32, 32)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_wkv_scan(shape, dtype):
    from repro.kernels.wkv_scan import wkv_pallas, wkv_ref
    t, h, dk, chunk = shape
    r = jax.random.normal(jax.random.PRNGKey(0), (t, h, dk), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (t, h, dk), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (t, h, dk), dtype)
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3),
                                         (t, h, dk), dtype))
    u = jax.random.normal(jax.random.PRNGKey(4), (h, dk), dtype)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (h, dk, dk), jnp.float32)
    y, s = wkv_pallas(r, k, v, w, u, s0, chunk=chunk)
    y0, s0_ = wkv_ref(*(x.astype(jnp.float32) for x in (r, k, v, w, u)),
                      s0)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0_), rtol=tol,
                               atol=tol)


def test_sparse_attention_equals_dense_when_all_selected():
    """Selecting every block must reproduce dense attention partials."""
    from repro.models import common as cm
    b, s, hk, dh, bs, h, t = 1, 128, 2, 32, 16, 4, 4
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, dh))
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh))
    nb = s // bs
    idx = jnp.broadcast_to(jnp.arange(nb)[None, None], (b, hk, nb))
    vlen = jnp.full((b, hk, nb), bs, jnp.int32)
    m, l, acc = ops.sparse_verify_attention(q, k, v, idx, vlen, bs)
    out_sparse = np.asarray(
        cm.combine_attn_parts([(m, l, acc)], jnp.float32))
    ref_out = np.asarray(cm.sdpa(q, k, v))
    np.testing.assert_allclose(out_sparse, ref_out, rtol=2e-5, atol=2e-5)


LAYERS = 3          # pool stack depth of the paged kernel tests


@pytest.mark.prefill
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_paged_prefill_attention_parity(shape, dtype, layer):
    """Pallas blockwise prefill kernel (interpret mode) reading one layer
    of a lane-dense pool stack vs the pure-jnp oracle over that layer
    alone, over shuffled page tables and ragged per-row chunk lengths."""
    s, hk, dh, bs, h, t = shape
    b = 2
    npg = s // bs + 1
    k = jax.random.normal(jax.random.PRNGKey(0),
                          (LAYERS, npg, bs, hk * dh), dtype)
    v = jax.random.normal(jax.random.PRNGKey(1),
                          (LAYERS, npg, bs, hk * dh), dtype)
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh), dtype)
    nb = npg - 1
    rng = np.random.default_rng(3)
    pt = jnp.asarray(np.stack([rng.permutation(np.arange(1, npg))[:nb]
                               for _ in range(b)]), jnp.int32)
    length = jnp.asarray([bs + bs // 2, 0], jnp.int32)   # resumed + fresh
    t_valid = jnp.asarray([t, max(t - 2, 1)], jnp.int32)
    a = ops.paged_prefill_attention(q, k, v, layer, pt, length, t_valid,
                                    use_pallas=True)
    b_ = ops.paged_prefill_attention(q, k[layer][None], v[layer][None], 0,
                                     pt, length, t_valid, use_pallas=False)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=tol,
                               atol=tol)


@pytest.mark.prefill
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_paged_prefill_attention_matches_flash(layer):
    """The kernel's normalised output must match the flash fallback over
    the gathered logical view (same masking, absolute causal positions)."""
    from repro.models import common as cm
    b, hk, dh, bs, h, t, nb = 2, 2, 16, 16, 4, 12, 5
    npg = nb * b + 1
    k = jax.random.normal(jax.random.PRNGKey(0), (LAYERS, npg, bs, hk * dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (LAYERS, npg, bs, hk * dh))
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh))
    pt = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], jnp.int32)
    length = jnp.asarray([20, 0], jnp.int32)
    t_valid = jnp.asarray([t, 7], jnp.int32)
    out = ops.paged_prefill_attention(q, k, v, layer, pt, length, t_valid,
                                      use_pallas=True)
    kl = k[layer].reshape(npg * bs, hk, dh)[
        (pt[:, :, None] * bs + jnp.arange(bs)[None, None]).reshape(b, -1)]
    vl = v[layer].reshape(npg * bs, hk, dh)[
        (pt[:, :, None] * bs + jnp.arange(bs)[None, None]).reshape(b, -1)]
    kv_pos = jnp.broadcast_to(jnp.arange(nb * bs)[None], (b, nb * bs))
    kv_valid = kv_pos < (length + t_valid)[:, None]
    positions = length[:, None] + jnp.arange(t)[None]
    ref_out = cm.flash_attention(q, kl, vl, q_positions=positions,
                                 kv_positions=kv_pos, causal=True,
                                 kv_valid=kv_valid, chunk=512)
    rows = jnp.arange(t)[None] < t_valid[:, None]   # pad rows are garbage
    np.testing.assert_allclose(
        np.asarray(jnp.where(rows[..., None, None], out, 0.0)),
        np.asarray(jnp.where(rows[..., None, None], ref_out, 0.0)),
        rtol=2e-5, atol=2e-5)
