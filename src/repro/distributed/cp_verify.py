"""All-gather-free sequence-parallel verification attention.

Generalizes the ``cp_retrieval.py`` pattern to the *whole* verify
family.  With the full KV cache sequence-sharded over a mesh axis, the
naive distributed verify all-gathers the keys/values every step (for
the retrieval path, the selected blocks — ~100 MB per refresh at paper
scale).  Here nothing KV-sized ever crosses the interconnect:

  per shard:  attention over ONLY the locally-resident tokens/pages
              -> flash-style softmax partials ``(m, l, acc)``
  combine:    one pmax/psum merge of the partials
              (``psum_softmax_merge`` — a few hundred KB per tick)

The merge is exact: softmax over a concatenation of key sets equals
the rescaled combination of per-set partials (the flash-attention
identity), so sharding the *full* verify is lossless.  Only the
retrieval path's top-k is approximated (top-(budget/shards) per shard
instead of global top-k — see ``cp_retrieval.py``).

``merged_partials_bytes`` / ``gathered_blocks_bytes`` model the
per-tick interconnect traffic of the two designs so benchmarks report
measured-model ratios instead of asserting the win
(``benchmarks/bench_serving.py --sharded``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# the softmax-partials merge (shared with cp_retrieval)
# ---------------------------------------------------------------------------

def psum_softmax_merge(m, l, acc, axis: str):
    """Merge per-shard flash partials across mesh axis `axis`.

    m/l: [..., T] running max / normalizer, acc: [..., T, Dh] weighted
    value sum.  A shard with no valid keys contributes ``m = -inf`` and
    zero ``l``/``acc``; its correction factor underflows to exactly 0,
    so empty shards are no-ops in the merge.  Returns the combined
    attention output ``acc / l`` (the only cross-shard collective in
    the verify path)."""
    m_g = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis)
    acc_g = jax.lax.psum(acc * corr[..., None], axis)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# shard-local full verify (FULL / REFRESH modes)
# ---------------------------------------------------------------------------

def _local_full_attention(q, k_loc, v_loc, length, shard_idx,
                          shard_tokens: int):
    """One shard's softmax partials over its local key range.

    q: [B, T, H, Dh] (replicated); k_loc/v_loc: [B, S_loc, Hk, Dh];
    length: [B] global valid length.  Validity of local position ``j``
    is ``shard_idx * shard_tokens + j < length``.  Returns
    (m, l: [B, H, T], acc: [B, H, T, Dh]) in fp32."""
    b, t, h, dh = q.shape
    s_loc, hk = k_loc.shape[1], k_loc.shape[2]
    g = h // hk
    pos = shard_idx * shard_tokens + jnp.arange(s_loc)
    valid = pos[None, :] < length[:, None]                   # [B, S_loc]
    qg = q.reshape(b, t, hk, g, dh).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bskd->bkgts", qg,
                    k_loc.astype(jnp.float32)) * (dh ** -0.5)
    sc = jnp.where(valid[:, None, None, None, :], sc, -jnp.inf)
    m = sc.max(-1)                                           # [B,Hk,G,T]
    p = jnp.exp(sc - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    p = jnp.where(valid[:, None, None, None, :], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bkgts,bskd->bkgtd", p, v_loc.astype(jnp.float32))
    return (m.reshape(b, h, t), l.reshape(b, h, t),
            acc.reshape(b, h, t, dh))


def cp_full_verify_attention(mesh, axis: str, q, k_cache, v_cache, length):
    """Sequence-parallel FULL-mode verify: q [B, T, H, Dh] replicated,
    k_cache/v_cache [B, S, Hk, Dh] with S sharded over `axis`, length
    [B] global.  Each shard attends only its resident keys; one
    ``psum_softmax_merge`` combines the partials.  Bit-exact in the
    flash sense (no key-axis reassociation beyond the per-shard splits)
    and zero KV bytes on the interconnect."""
    n_shards = mesh.shape[axis]
    shard_tokens = k_cache.shape[1] // n_shards

    def body(q_, k_, v_, ln_):
        sid = jax.lax.axis_index(axis)
        m, l, acc = _local_full_attention(q_, k_, v_, ln_, sid,
                                          shard_tokens)
        out = psum_softmax_merge(m, l, acc, axis)            # [B,H,T,Dh]
        return out.transpose(0, 2, 1, 3).astype(q_.dtype)    # [B,T,H,Dh]

    seq_spec = P(None, axis, None, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), seq_spec, seq_spec, P()),
                       out_specs=P(), check_vma=False)
    return fn(q, k_cache, v_cache, length)


# ---------------------------------------------------------------------------
# per-tick interconnect traffic model (merge vs gather)
# ---------------------------------------------------------------------------

def merged_partials_bytes(batch: int, q_tokens: int, num_heads: int,
                          head_dim: int, num_layers: int,
                          n_shards: int) -> int:
    """Interconnect bytes per tick for the partials merge.

    Each layer all-reduces one fp32 message of ``(m, l, acc)`` =
    ``B*H*T*(2 + Dh)`` floats per shard; a ring all-reduce moves
    ~``2*(n-1)/n`` of the message per link, so total link traffic is
    ``2*(n_shards - 1) * message`` per layer.  Zero when unsharded."""
    if n_shards <= 1:
        return 0
    msg = batch * num_heads * q_tokens * (2 + head_dim) * 4
    return 2 * (n_shards - 1) * msg * num_layers


def gathered_blocks_bytes(budget_blocks: int, block_size: int,
                          num_kv_heads: int, head_dim: int,
                          num_layers: int, n_shards: int,
                          kv_itemsize: int = 2) -> int:
    """Interconnect bytes per tick for the baseline design: all-gather
    the selected K/V blocks so every shard verifies against the whole
    selection.  Each shard must receive the ``(n-1)/n`` remote share of
    ``budget_blocks`` blocks (K and V), every layer — the ~100 MB per
    refresh the paper-scale estimate in ``cp_retrieval.py`` quotes."""
    if n_shards <= 1:
        return 0
    sel = budget_blocks * block_size * num_kv_heads * head_dim * 2 \
        * kv_itemsize * num_layers
    return (n_shards - 1) * sel


def verify_traffic_report(*, batch: int, q_tokens: int, num_heads: int,
                          num_kv_heads: int, head_dim: int,
                          num_layers: int, n_shards: int,
                          budget_blocks: int, block_size: int,
                          kv_itemsize: int = 2) -> dict:
    """Per-tick cross-shard traffic of the merge path vs the modelled
    gathered-block volume, plus their ratio (the ``--sharded`` bench's
    ≥10x acceptance check)."""
    merged = merged_partials_bytes(batch, q_tokens, num_heads, head_dim,
                                   num_layers, n_shards)
    gathered = gathered_blocks_bytes(budget_blocks, block_size,
                                     num_kv_heads, head_dim, num_layers,
                                     n_shards, kv_itemsize)
    return dict(merged_partials_bytes=merged,
                gathered_blocks_bytes=gathered,
                traffic_ratio=(gathered / merged) if merged else 0.0)
