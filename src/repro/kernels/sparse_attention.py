"""Pallas TPU kernel: block-routed flash attention over a paged KV pool —
the paper's hot spot (verification attention) and chunked prefill.

The block ids to read (a slot's page table, or the retrieval-selected
blocks of its partial cache) arrive via *scalar prefetch*, and the KV
BlockSpec index_map uses them so the pipeline streams exactly those
128-token KV tiles HBM->VMEM — neither the partial cache nor a gathered
``[B, S_max]`` view is ever materialised.  Running (m, l, acc) live in
VMEM scratch; the final grid step emits softmax partials that the caller
merges with the small buffer/tree segment
(``models.common.combine_attn_parts``).

Layout.  The K/V operand is the stored page pool itself: a stack of
per-layer pools ``[L, NB, bs, Hk*Dh]``, lane-dense (kv head ``h`` owns
columns ``h*Dh:(h+1)*Dh``), plus the layer to read as a scalar-prefetch
operand.  Grid step ``(h, j)`` streams the ``(bs, Dh)`` tile of page
``block_idx[h, j]`` of that layer at column block ``h`` — both tile dims
are TPU-aligned, and nothing copies the pool before the call.  A kv
head's grouped queries are flattened to ``R = rep*T`` rows so each grid
step is two plain 2-D MXU matmuls ``[R, Dh] x [Dh, bs]`` and
``[R, bs] x [bs, Dh]``.

Masking.  Key ``s`` of grid block ``j`` is valid while
``s < block_valid_len[h, j]``; with ``q_pos`` given (prefill) it must
also satisfy the causal bound ``j*bs + s <= q_pos`` in absolute
positions — grid coord ``j`` IS the logical block — so in-chunk
self-attention falls out of the same scan.

Grid: (Hk, N).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.names import named

NEG = -1e30


def _kernel(layer_ref, idx_ref, vlen_ref, *refs, block_size: int, nblk: int,
            per_head: bool, causal: bool):
    if causal:
        qpos_ref, *refs = refs
    q_ref, k_ref, v_ref, m_out, l_out, acc_out, m_s, l_s, acc_s = refs
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                      # [R, Dh]
    k = k_ref[...].astype(jnp.float32)                    # [bs, Dh]
    v = v_ref[...].astype(jnp.float32)                    # [bs, Dh]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [R, bs]
    s_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    ok = s_pos < vlen_ref[h if per_head else 0, j]
    if causal:
        ok = ok & (j * block_size + s_pos <= qpos_ref[...])
    logits = jnp.where(ok, logits, NEG)

    m_prev = m_s[...]                                     # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(logits - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == nblk - 1)
    def _emit():
        m_out[0] = m_s[...]
        l_out[0] = l_s[...]
        acc_out[0] = acc_s[...]


def block_attention_pallas(q, k_pool, v_pool, layer, block_idx,
                           block_valid_len, q_pos: Optional[jax.Array] = None,
                           *, interpret: bool = True,
                           name: str = "specpv_attn_blocks"):
    """Softmax partials of ``q`` over routed KV blocks.

    q: [T, H, Dh]; k_pool/v_pool: [L, NB, bs, Hk*Dh], a stack of page
    pools stored lane-dense (the paged trunk pool, or a contiguous cache
    viewed as one layer of blocks), read in place; layer: int32 scalar,
    the stack entry to read; block_idx/block_valid_len: [Hi, N] int32
    with Hi = Hk (per-kv-head routing) or 1 (every head reads the same
    blocks) — grid step j reads block ``block_idx[h, j]`` and keeps its
    first ``block_valid_len[h, j]`` keys; q_pos: [T] int32 absolute
    query positions, given for causal (prefill) attention where grid
    step j is logical block j.  ``Hk`` is the pool's width over q's
    ``Dh``.  ``name`` names the kernel in the compiled program and the
    profiler trace (``kernels/names.py``); under ``vmap`` it runs once
    per row.

    Returns softmax partials (m [H, T], l [H, T], acc [H, T, Dh]) fp32."""
    t, h, dh = q.shape
    nl, nb, bs, width = k_pool.shape
    hk = width // dh
    hi, nblk = block_idx.shape
    rep = h // hk
    r = rep * t
    causal = q_pos is not None
    scale = 1.0 / math.sqrt(dh)
    qg = (q.reshape(t, hk, rep, dh).transpose(1, 2, 0, 3)
          * scale).reshape(hk, r, dh)                      # [Hk, R, Dh]

    def kv_map(hh, jj, lyr, idx, *_):
        return lyr[0], idx[hh if hi > 1 else 0, jj], 0, hh

    def head_map(hh, jj, *_):
        return hh, 0, 0

    in_specs = [pl.BlockSpec((1, r, dh), head_map),
                pl.BlockSpec((None, None, bs, dh), kv_map),
                pl.BlockSpec((None, None, bs, dh), kv_map)]
    operands = [qg, k_pool, v_pool]
    if causal:
        in_specs.insert(0, pl.BlockSpec((r, 1), lambda *_: (0, 0)))
        operands.insert(0, jnp.tile(q_pos.astype(jnp.int32), rep)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, nblk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, r, 1), head_map),
                   pl.BlockSpec((1, r, 1), head_map),
                   pl.BlockSpec((1, r, dh), head_map)],
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, dh), jnp.float32)],
    )
    out_shape = [jax.ShapeDtypeStruct((hk, r, 1), jnp.float32),
                 jax.ShapeDtypeStruct((hk, r, 1), jnp.float32),
                 jax.ShapeDtypeStruct((hk, r, dh), jnp.float32)]
    fn = named(name, pl.pallas_call(
        functools.partial(_kernel, block_size=bs, nblk=nblk,
                          per_head=hi > 1, causal=causal),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret),
        rows=True)
    lyr = jnp.clip(jnp.asarray(layer, jnp.int32), 0, nl - 1).reshape(1)
    idx = jnp.clip(block_idx.astype(jnp.int32), 0, nb - 1)
    m, l, acc = fn(lyr, idx, block_valid_len.astype(jnp.int32), *operands)
    return (m.reshape(h, t), l.reshape(h, t), acc.reshape(h, t, dh))
