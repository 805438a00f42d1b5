"""Distributed retrieval (shard_map): on a single-shard mesh the
context-parallel partial attention must equal the global top-k reference.
(Multi-shard behaviour is exercised by the 256-device hillclimb lowering.)
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SpecPVConfig
from repro.distributed.cp_retrieval import cp_partial_verify_attention
from repro.kernels import ref
from repro.launch.mesh import make_mesh
from repro.models import common as cm


def test_cp_retrieval_single_shard_matches_global():
    mesh = make_mesh((1,), ("model",))
    spec = SpecPVConfig(block_size=16)
    b, s, hk, dh, h, t = 1, 128, 2, 32, 4, 3
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, dh))
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh))
    length = jnp.asarray([100], jnp.int32)
    km, kn = jax.vmap(lambda kk, ll: ref.block_summary_ref(kk, ll, 16))(
        k, length)
    budget = 4
    with jax.set_mesh(mesh):
        out = cp_partial_verify_attention(mesh, "model", spec, budget,
                                          q, k, v, km, kn, length)
    nb = s // 16
    sc = jax.vmap(ref.retrieval_score_ref)(q, km, kn, jnp.ones((b, t)))
    nvalid = jnp.clip(length[:, None] - jnp.arange(nb) * 16, 0, 16)
    scm = jnp.where((nvalid > 0)[:, None, :], sc, -jnp.inf)
    _, idx = jax.lax.top_k(scm, budget)
    vlen = jnp.take_along_axis(
        jnp.broadcast_to(nvalid[:, None], (b, hk, nb)), idx, axis=-1)
    m, l, acc = jax.vmap(
        lambda *a: ref.sparse_verify_attention_ref(*a, block_size=16))(
        q, k, v, idx, vlen)
    out_ref = cm.combine_attn_parts([(m, l, acc)], jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
