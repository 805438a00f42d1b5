"""Distributed (context-parallel) SpecPV retrieval + partial attention
via shard_map — the beyond-paper optimization promised in DESIGN.md §3.

With the full KV cache sequence-sharded over a mesh axis, the baseline
refresh step *gathers* the selected blocks to every chip (≈110 MB per
refresh for deepseek @ 500K).  This module keeps the selected blocks
shard-local instead:

  per shard:  score local block summaries (paper eqs. 1-3)
           -> local top-(budget/shards) selection
           -> block-sparse attention over the local selection
  combine:    one psum-style softmax merge of (m, l, acc) partials
              (a few hundred KB, vs the multi-MB gather)

Selection semantics change slightly (top-k per shard instead of global
top-k — a standard distributed-top-k approximation; with blocks spread
round-robin the two agree in expectation).  Recorded as §Perf case D.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SpecPVConfig
from repro.distributed.cp_verify import psum_softmax_merge
from repro.kernels import ref as kref


def _local_partial_attention(spec: SpecPVConfig, budget_local: int,
                             q, k_loc, v_loc, kmax_loc, kmin_loc, length,
                             shard_idx, shard_tokens, axis: str):
    """Body executed per shard.  q: [B, T, H, Dh] (replicated);
    k_loc/v_loc: [B, S_loc, Hk, Dh]; kmax/kmin: [B, NB_loc, Hk, Dh];
    length: [B] global length.  Returns merged attention out [B,T,H,Dh]."""
    b, t, h, dh = q.shape
    s_loc, hk = k_loc.shape[1], k_loc.shape[2]
    bs = spec.block_size
    nb_loc = kmax_loc.shape[1]
    # local block validity: global token range of this shard
    start = shard_idx * shard_tokens
    blk_start = start + jnp.arange(nb_loc) * bs
    n_valid = jnp.clip(length[:, None] - blk_start[None], 0, bs)  # [B, NB]

    # eq. (2)/(3): mean reduction over queries, grouped heads
    qg = q.reshape(b, t, hk, h // hk, dh).astype(jnp.float32)
    smax = jnp.einsum("btkrd,bnkd->btkrn", qg, kmax_loc.astype(jnp.float32))
    smin = jnp.einsum("btkrd,bnkd->btkrn", qg, kmin_loc.astype(jnp.float32))
    s = jnp.maximum(smax, smin).mean(axis=(1, 3))          # [B, Hk, NB]
    s = jnp.where((n_valid > 0)[:, None, :], s, -jnp.inf)
    k_sel = min(budget_local, nb_loc)
    _, idx = jax.lax.top_k(s, k_sel)                       # [B, Hk, k]
    vlen = jnp.take_along_axis(
        jnp.broadcast_to(n_valid[:, None], (b, hk, nb_loc)), idx, axis=-1)

    m, l, acc = jax.vmap(
        functools.partial(kref.sparse_verify_attention_ref,
                          block_size=bs))(q, k_loc, v_loc, idx, vlen)
    # softmax merge across shards (the only cross-shard traffic; see
    # cp_verify.py for the traffic model)
    out = psum_softmax_merge(m, l, acc, axis)              # [B, H, T, Dh]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # [B, T, H, Dh]


def cp_partial_verify_attention(mesh, axis: str, spec: SpecPVConfig,
                                budget_blocks: int,
                                q, k_cache, v_cache, kmax, kmin, length):
    """q: [B, T, H, Dh] replicated; k_cache/v_cache: [B, S, Hk, Dh] with S
    sharded over `axis`; kmax/kmin: [B, NB, Hk, Dh] likewise; length [B].
    Returns attention output [B, T, H, Dh] (replicated)."""
    n_shards = mesh.shape[axis]
    s = k_cache.shape[1]
    shard_tokens = s // n_shards
    budget_local = max(1, budget_blocks // n_shards)

    def body(q_, k_, v_, kx_, kn_, ln_):
        sid = jax.lax.axis_index(axis)
        return _local_partial_attention(spec, budget_local, q_, k_, v_,
                                        kx_, kn_, ln_, sid, shard_tokens,
                                        axis)

    seq_spec = P(None, axis, None, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), seq_spec, seq_spec, seq_spec, seq_spec,
                                 P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(q, k_cache, v_cache, kmax, kmin, length)
