"""Jit'd public wrappers over the Pallas kernels with batch handling and
an automatic interpret-mode fallback on non-TPU backends.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs step-by-step exactly as the TPU grid would, which is
what the correctness sweeps in tests/test_kernels.py validate.

Each call site names its kernel in the compiled program and the
profiler trace (``kernels/names.py``): ``specpv_attn_full`` (paged
full / refresh verification), ``specpv_attn_partial`` (zero-copy
partial verification), ``specpv_attn_prefill`` (paged prefill),
``specpv_attn_blocks`` (block-sparse over a contiguous cache),
``specpv_retrieval_score`` and ``specpv_block_summary``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_summary import block_summary_pallas
from repro.kernels.retrieval_score import retrieval_score_pallas
from repro.kernels.sparse_attention import block_attention_pallas
from repro.kernels import ref
from repro.models import common as cm


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_shard(fn, args, in_dims, out_dims, heads: int):
    """``fn(*args)``, run per shard of the ambient mesh (``jax.set_mesh``).

    XLA cannot partition a Mosaic kernel, so under a mesh the call goes
    through ``shard_map``: head-parallel over ``model`` when that axis
    divides the ``heads`` kv heads (``in_dims`` gives each operand's
    head axis, None = replicated — a lane-dense pool's ``Hk*Dh`` axis,
    whose ``model`` pieces are then whole heads; ``out_dims`` each
    output's, in the outputs' structure), otherwise every device
    computes the whole call.
    Without a mesh it is ``fn(*args)``."""
    from jax.sharding import PartitionSpec as P
    mesh = cm.current_mesh()
    if mesh is None:
        return fn(*args)
    ax = ("model" if "model" in mesh.axis_names
          and heads % mesh.shape["model"] == 0 else None)

    def spec(ndim, d):
        dims = [None] * ndim
        if d is not None:
            dims[d] = ax
        return P(*dims)

    out_specs = jax.tree.map(lambda o, d: spec(o.ndim, d),
                             jax.eval_shape(fn, *args), out_dims)
    in_specs = tuple(spec(a.ndim, d) for a, d in zip(args, in_dims))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


@functools.partial(jax.jit, static_argnames=("block_size", "use_pallas"))
def block_summaries(k, length, block_size: int = 128,
                    use_pallas: bool = True):
    """Batched summaries.  k: [B, S, Hk, Dh]; length: [B].
    Returns (kmax, kmin): [B, NB, Hk, Dh] fp32."""
    fn = (functools.partial(block_summary_pallas, block_size=block_size,
                            interpret=_interpret())
          if use_pallas else
          functools.partial(ref.block_summary_ref, block_size=block_size))
    return jax.vmap(fn)(k, length)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def retrieval_scores(q, kmax, kmin, q_weight, use_pallas: bool = True):
    """Batched Quest scores.  q: [B, T, H, Dh]; kmax/kmin: [B, NB, Hk, Dh];
    q_weight: [B, T].  Returns [B, Hk, NB] fp32."""
    fn = (functools.partial(retrieval_score_pallas, interpret=_interpret())
          if use_pallas else ref.retrieval_score_ref)
    return _per_shard(jax.vmap(fn), (q, kmax, kmin, q_weight),
                    (2, 2, 2, None), 1, kmax.shape[2])


def _block_attention(q, k_pool, v_pool, layer, block_idx, block_valid_len,
                     q_pos=None, *, use_pallas: bool, name: str):
    """One row: the block-attention kernel named ``name`` (each call
    site its own, ``kernels/names.py``), or its ``ref`` oracle behind
    the kernel's signature (``sparse_attention.block_attention_pallas``:
    a lane-dense pool stack ``[L, NB, bs, Hk*Dh]`` and a layer index)."""
    if use_pallas:
        return block_attention_pallas(q, k_pool, v_pool, layer, block_idx,
                                      block_valid_len, q_pos,
                                      interpret=_interpret(), name=name)
    _, nb, bs, width = k_pool.shape
    dh = q.shape[-1]
    kf = k_pool[layer].reshape(nb * bs, width // dh, dh)
    vf = v_pool[layer].reshape(nb * bs, width // dh, dh)
    shape = (width // dh, block_idx.shape[1])
    idx = jnp.broadcast_to(block_idx, shape)
    vlen = jnp.broadcast_to(block_valid_len, shape)
    if q_pos is None:
        return ref.sparse_verify_attention_ref(q, kf, vf, idx, vlen,
                                               block_size=bs)
    return ref.paged_prefill_attention_ref(q, kf, vf, idx, vlen, q_pos[:1],
                                           block_size=bs)


def _kv_heads(pool, q):
    """Kv heads of a lane-dense pool ``[..., Hk*Dh]`` read by ``q``
    ``[..., H, Dh]``."""
    return pool.shape[-1] // q.shape[-1]


@functools.partial(jax.jit, static_argnames=("block_size", "use_pallas"))
def sparse_verify_attention(q, k_cache, v_cache, block_idx, block_valid_len,
                            block_size: int = 128, use_pallas: bool = True):
    """Batched block-sparse verification attention partials.

    q: [B, T, H, Dh]; caches: [B, S, Hk, Dh]; idx/vlen: [B, Hk, NSel].
    Each row's cache is viewed as a one-layer block pool
    ``[1, S/block, block, Hk*Dh]``.
    Returns (m [B, H, T], l [B, H, T], acc [B, H, T, Dh])."""
    b, s, hk, dh = k_cache.shape
    nb = s // block_size
    kb = k_cache[:, : nb * block_size].reshape(b, 1, nb, block_size, hk * dh)
    vb = v_cache[:, : nb * block_size].reshape(b, 1, nb, block_size, hk * dh)
    fn = functools.partial(_block_attention, use_pallas=use_pallas,
                           name="specpv_attn_blocks")
    return jax.vmap(fn, in_axes=(0, 0, 0, None, 0, 0))(
        q, kb, vb, 0, block_idx, block_valid_len)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def paged_verify_attention(q, pool_k, pool_v, layer, page_table, length,
                           use_pallas: bool = True):
    """Paged dense/refresh verification attention over the shared block
    pool (softmax partials).

    Reuses the block-attention kernel's scalar-prefetch index_map: the
    slot's page table IS the block-id table, so the pipeline streams
    exactly the resident pages HBM->VMEM — the contiguous [B, S, ...]
    view is never materialised.  Per-block valid lengths are derived
    from `length`, so pages past the filled region contribute nothing.

    The per-row page counts are *ragged*: a row only streams the
    ``ceil(length / block)`` leading table entries that actually hold
    tokens — every empty block's index is rewritten to the reserved
    null page 0 before prefetch, so the decode reserve and (in the
    fused multi-mode step, where partial-mode rows pass ``length = 0``)
    entire rows collapse to re-reads of one resident page instead of
    pulling their whole table through the pipeline.

    q: [B, T, H, Dh]; pool_k/pool_v: [L, NP, block, Hk*Dh], the stored
    lane-dense pool stack, read in place; layer: int32 scalar, the layer
    to read; page_table: [B, NB] int32; length: [B] — the fused step
    passes a per-row *effective* length (0 for rows whose verification
    reads the partial cache instead).
    Returns (m [B, H, T], l [B, H, T], acc [B, H, T, Dh]) fp32 —
    combinable with the tree self-segment via
    ``models.common.combine_attn_parts``."""
    bs = pool_k.shape[2]
    nb = page_table.shape[1]
    vlen = jnp.clip(length[:, None] - jnp.arange(nb)[None] * bs, 0, bs)
    # ragged routing: empty blocks stream the null page (their valid
    # length is 0, so the masked tile contributes nothing either way)
    routed = jnp.where(vlen > 0, page_table, 0)
    fn = jax.vmap(functools.partial(_block_attention, use_pallas=use_pallas,
                                    name="specpv_attn_full"),
                  in_axes=(0, None, None, None, 0, 0))
    return _per_shard(fn, (q, pool_k, pool_v, jnp.asarray(layer, jnp.int32),
                         routed[:, None].astype(jnp.int32),
                         vlen[:, None].astype(jnp.int32)),
                    (2, 3, 3, None, None, None), (1, 1, 1),
                    _kv_heads(pool_k, q))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def routed_partial_attention(q, pool_k, pool_v, layer, block_idx,
                             block_valid_len, use_pallas: bool = True):
    """Zero-copy partial verification attention: the retrieval-selected
    blocks are read *in place* from the shared block pool through a
    per-row, per-kv-head page list — no dense partial cache exists.

    The caller has already translated the slot's selected logical
    blocks through its live page table (a physical page id IS a block
    index into the flattened pool), zeroed unused selection slots
    (``block_valid_len == 0`` masks them), and clipped the last
    selected block's valid length to the row's committed extent — so
    this is exactly the block-sparse kernel's contract, streaming only
    the ~budget tokens actually attended.  RoPE was applied to K at
    pool-write time, so retrieved blocks keep their true positions for
    free.

    q: [B, T, H, Dh]; pool_k/pool_v: [L, NP, block, Hk*Dh], the stored
    lane-dense pool stack; layer: int32 scalar, the layer to read;
    block_idx: [B, Hk, NSel] routed physical page ids;
    block_valid_len: [B, Hk, NSel] valid tokens per selected block.
    Returns (m [B, H, T], l [B, H, T], acc [B, H, T, Dh]) fp32 —
    combinable with the tail-buffer and tree self-segments via
    ``models.common.merge_attn_partials``/``combine_attn_parts``."""
    fn = jax.vmap(functools.partial(_block_attention, use_pallas=use_pallas,
                                    name="specpv_attn_partial"),
                  in_axes=(0, None, None, None, 0, 0))
    return _per_shard(fn, (q, pool_k, pool_v, jnp.asarray(layer, jnp.int32),
                         block_idx.astype(jnp.int32),
                         block_valid_len.astype(jnp.int32)),
                    (2, 3, 3, None, 1, 1), (1, 1, 1), _kv_heads(pool_k, q))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def paged_prefill_attention(q, pool_k, pool_v, layer, page_table, length,
                            t_valid, use_pallas: bool = True):
    """Blockwise-parallel paged prefill attention over the shared block
    pool — the batched-prefill counterpart of ``paged_verify_attention``.

    The caller has already scattered the chunk's K/V into the pool
    (``kvcache.cache.paged_write_tokens``), so each row's context —
    previous chunks AND the chunk itself — is exactly the filled prefix
    of its page table.  The kernel scans the row's logical blocks with
    carry-based softmax rescaling and an absolute-position causal mask
    (key ``j*bs + s`` vs query ``length + i``), so in-chunk
    self-attention needs no separate part and the contiguous
    ``[B, S, ...]`` gathered view never materialises.  Blocks past the
    filled region route to the reserved null page 0 and are fully
    masked.

    q: [B, T, H, Dh] (the tick's packed chunk queries);
    pool_k/pool_v: [L, NP, block, Hk*Dh], a lane-dense pool stack (the
    trunk passes the layer it just wrote as a one-layer stack); layer:
    int32 scalar, the layer to read; page_table: [B, NB] int32;
    length: [B] tokens already resident
    *before* this chunk; t_valid: [B] real (non-pad) chunk tokens per
    row — pad queries produce garbage rows the caller's feature masking
    discards.
    Returns normalised attention [B, T, H, Dh] in q's dtype (same
    contract as the flash fallback)."""
    bs = pool_k.shape[2]
    t = q.shape[1]
    nb = page_table.shape[1]
    end = length + t_valid
    vlen = jnp.clip(end[:, None] - jnp.arange(nb)[None] * bs, 0, bs)
    routed = jnp.where(vlen > 0, page_table, 0)
    q_pos = length.astype(jnp.int32)[:, None] + jnp.arange(t)[None]
    fn = jax.vmap(functools.partial(_block_attention, use_pallas=use_pallas,
                                    name="specpv_attn_prefill"),
                  in_axes=(0, None, None, None, 0, 0, 0))
    m, l, acc = _per_shard(fn, (q, pool_k, pool_v,
                              jnp.asarray(layer, jnp.int32),
                              routed[:, None].astype(jnp.int32),
                              vlen[:, None].astype(jnp.int32), q_pos),
                         (2, 3, 3, None, None, None, None), (1, 1, 1),
                         _kv_heads(pool_k, q))
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # [B, H, T, Dh]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
