"""Verification-step plumbing: input assembly, cache commits, buffer
writes, and the post-commit partial refresh (paper §3.2-3.3).

Verify-input layout (attention archs):

  full/partial step:   [ x_b | tree nodes ]                (S = 1 + T)
  refresh step:        [ pending (padded to Pmax) | tree ] (S = Pmax + T)
  fused step:          per ROW one of the above, packed inside a single
                       static shape (``build_verify_inputs_fused``) —
                       live operands keep their single-mode lane
                       positions, only trailing zeros are appended

``pending`` are accepted tokens whose exact full-context KV is not in the
full cache yet (all tokens accepted under partial verification since the
last refresh, ending with the newest bonus x_b).  The pkv *buffer* holds
the approximate KV of pending[:-1].

Chain-shaped and sampled rows need NO layout change: a chain is the
rank-0 path of the engine's tree (``TreeSpec.chain_mask``), already
present in every per-row verify layout, and the tree's ancestor self-mask
isolates it — acceptance masks candidates per row (``node_valid``), the
packing here is oblivious.  Commit epilogues are masked per row by the
accepted path, so mixed chain/tree/sampled ticks share one dispatch.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SpecPVConfig
from repro.core.tree import TreeSpec
from repro.models.dense import (quest_block_scores, select_and_gather_partial,
                                select_and_gather_partial_paged)
from repro.kvcache.cache import (update_layer_summaries, paged_write_layers,
                                 paged_update_summaries)


# ---------------------------------------------------------------------------
# input assembly
# ---------------------------------------------------------------------------

def build_verify_inputs(tree: TreeSpec, pending, pending_len, tree_tokens,
                        seq_len, active=None):
    """Assemble the verify input for a step.

    pending: [B, P] left-aligned tokens (P = 1 for full/partial steps);
    pending_len: [B] valid count (>= 1); tree_tokens: [B, T];
    seq_len: [B] total accepted tokens so far (prompt + generated);
    active: optional [B] bool — dead batch slots (continuous batching).
    Dead rows get an all-False self mask and empty pending validity, so
    nothing they compute can be committed and no garbage positions leak
    into attention.

    Returns dict with tokens [B,S], positions [B,S], self_mask [B,S,S],
    q_valid [B,S], root_slot [B], node_slots [B,T].
    """
    b, p = pending.shape
    t = tree.size
    s = p + t
    tokens = jnp.concatenate([pending, tree_tokens], axis=1)

    pend_valid = jnp.arange(p)[None] < pending_len[:, None]       # [B, P]
    if active is not None:
        pend_valid = pend_valid & active[:, None]
    valid = jnp.concatenate([pend_valid,
                             jnp.ones((b, t), bool)], axis=1)     # [B, S]
    if active is not None:
        valid = valid & active[:, None]

    # positions: pending token i sits at seq_len - pending_len + i;
    # tree node n sits at seq_len + depth(n)
    pend_pos = seq_len[:, None] - pending_len[:, None] + jnp.arange(p)[None]
    depths = jnp.asarray(tree.depths_arr())
    node_pos = seq_len[:, None] + depths[None]
    positions = jnp.concatenate([pend_pos, node_pos], axis=1)
    positions = jnp.maximum(positions, 0)

    # self mask
    anc = jnp.asarray(tree.ancestor_mask())                       # [T, T]
    m = jnp.zeros((b, s, s), bool)
    causal_pp = (jnp.arange(p)[None, :, None] >= jnp.arange(p)[None, None, :])
    m = m.at[:, :p, :p].set(causal_pp & pend_valid[:, None, :]
                            & pend_valid[:, :, None])
    m = m.at[:, p:, :p].set(pend_valid[:, None, :])               # tree->pend
    m = m.at[:, p:, p:].set(jnp.broadcast_to(anc[None], (b, t, t)))
    if active is not None:
        m = m & active[:, None, None]

    root_slot = pending_len - 1                                   # [B]
    node_slots = jnp.broadcast_to(p + jnp.arange(t)[None], (b, t))
    return dict(tokens=tokens, positions=positions, self_mask=m,
                q_valid=valid, root_slot=root_slot, node_slots=node_slots,
                pend_valid=pend_valid)


def build_verify_inputs_fused(tree: TreeSpec, pending, pending_len, p_eff,
                              tree_tokens, seq_len, active=None):
    """Per-row-layout verify input for the fused multi-mode step.

    Every row packs its sequence as ``[pend (p_eff) | tree (T) | pad]``
    inside one static width ``S = P + T``: refresh rows use the full
    pending width (``p_eff = P``, the grouped refresh layout), while
    full/partial rows collapse the pend region to one slot
    (``p_eff = 1``), so their live tokens occupy the *same contiguous
    prefix* a narrow per-mode step would use, followed by zero padding.
    Keeping live operands in identical lane positions (only trailing
    zeros appended) is what makes the fused step's reductions — and
    therefore its greedy outputs — bit-identical to the grouped
    per-mode path; scattering them (e.g. tree always at offset P) would
    reassociate the key-axis sums and break losslessness.

    pending: [B, P] (P = 1 when no refresh row steps this tick);
    pending_len: [B] valid pend count per row (<= p_eff);
    p_eff: [B] int32 per-row pend width in {1, P};
    tree_tokens: [B, T]; seq_len: [B]; active: optional [B] bool.

    Returns the same dict as ``build_verify_inputs`` — positions, self
    mask, root/node slots are all per-row, so downstream gathers
    (acceptance, commits, the refresh q_weight scatter) need no layout
    knowledge beyond ``node_slots``/``root_slot``.
    """
    b, p = pending.shape
    t = tree.size
    s = p + t
    p_eff = p_eff[:, None]                                        # [B, 1]
    sidx = jnp.arange(s)[None]                                    # [1, S]
    pend_q = sidx < p_eff                                         # [B, S]
    tree_q = (sidx >= p_eff) & (sidx < p_eff + t)
    tidx = jnp.clip(sidx - p_eff, 0, t - 1)                       # [B, S]

    pend_pad = jnp.pad(pending, ((0, 0), (0, t)))                 # [B, S]
    tree_g = jnp.take_along_axis(tree_tokens, tidx, axis=1)
    tokens = jnp.where(pend_q, pend_pad, jnp.where(tree_q, tree_g, 0))

    pend_valid_w = pend_q & (sidx < pending_len[:, None])         # [B, S]
    if active is not None:
        pend_valid_w = pend_valid_w & active[:, None]

    # positions: pend slot i at seq_len - pending_len + i; tree node n
    # at seq_len + depth(n) — per row, exactly as the grouped layouts
    depths = jnp.asarray(tree.depths_arr())
    pend_pos = seq_len[:, None] - pending_len[:, None] + sidx
    node_pos = seq_len[:, None] + jnp.take(depths, tidx)
    positions = jnp.where(pend_q, pend_pos,
                          jnp.where(tree_q, node_pos, 0))
    positions = jnp.maximum(positions, 0)

    anc = jnp.asarray(tree.ancestor_mask())                       # [T, T]
    anc_q = anc[tidx]                                             # [B, S, T]
    anc_qk = jnp.take_along_axis(
        anc_q, jnp.broadcast_to(tidx[:, None, :], (b, s, s)), axis=2)
    causal = sidx[:, :, None] >= sidx[:, None, :]                 # [1, S, S]
    m_pp = (causal & pend_valid_w[:, None, :] & pend_valid_w[:, :, None])
    m_tp = tree_q[:, :, None] & pend_valid_w[:, None, :]
    m_tt = tree_q[:, :, None] & tree_q[:, None, :] & anc_qk
    m = m_pp | m_tp | m_tt
    if active is not None:
        m = m & active[:, None, None]

    valid = pend_valid_w | tree_q
    if active is not None:
        valid = valid & active[:, None]
    root_slot = pending_len - 1                                   # [B]
    node_slots = p_eff + jnp.arange(t)[None]                      # [B, T]
    return dict(tokens=tokens, positions=positions, self_mask=m,
                q_valid=valid, root_slot=root_slot, node_slots=node_slots,
                pend_valid=pend_valid_w[:, :p])


def commit_slots(tree: TreeSpec, pend_valid, path_nodes, p):
    """Input slots to commit, compacted: valid pending first, then the
    accepted path.  Returns (slots [B, P+D], slot_valid [B, P+D]).

    ``p`` is the tree-region offset — a scalar for the uniform layouts,
    or a per-row [B] vector for the fused step's per-row layouts (the
    pend region is always the leading ``pend_valid.shape[1]`` slots)."""
    b, pw = pend_valid.shape
    d = tree.depth
    path_valid = path_nodes >= 0
    p = jnp.asarray(p, jnp.int32)
    p = p[:, None] if p.ndim else p
    path_slots = p + jnp.maximum(path_nodes, 0)
    slots = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(pw)[None], (b, pw)), path_slots],
        axis=1)
    valid = jnp.concatenate([pend_valid, path_valid], axis=1)
    # stable compaction: valid entries to the front, order preserved
    order = jnp.argsort(jnp.where(valid, 0, 1), axis=1, stable=True)
    slots = jnp.take_along_axis(slots, order, axis=1)
    valid = jnp.take_along_axis(valid, order, axis=1)
    return slots, valid


# ---------------------------------------------------------------------------
# commits
# ---------------------------------------------------------------------------

def commit_write_extent(pmax: int, tree_depth: int) -> int:
    """Upper bound on the full-cache tokens one verify commit can touch
    past the current length: the compacted commit window is
    ``pending (<= pmax) + accepted path (<= depth)`` wide, and both the
    token scatter and the targeted summary refresh write all of it
    (entries beyond the accepted count are overwritten later).

    This is the copy-on-write horizon: before a step, every physical
    block intersecting ``[length, length + extent)`` of a stepping slot
    must be exclusively owned (refcount 1), otherwise a partial-refresh
    commit into a shared block would perturb the other holders — the
    engine CoWs exactly this window (``SpecPVEngine.prepare_cow``)."""
    return pmax + tree_depth


def gather_new_kv(new_kv, slots, slot_valid):
    """new_kv: (k, v) [L, B, S, Hk, Dh]; slots: [B, W] -> [L, B, W, Hk, Dh].
    Invalid slots are zeroed (they land beyond the committed length)."""
    k, v = new_kv
    idx = slots[None, :, :, None, None]
    msk = slot_valid[None, :, :, None, None]

    def g(a):
        out = jnp.take_along_axis(
            a, jnp.broadcast_to(idx, (a.shape[0], a.shape[1], slots.shape[1],
                                      a.shape[3], a.shape[4])), axis=2)
        return jnp.where(msk, out, 0)
    return g(k), g(v)


def append_full_cache(cache: Dict, ck, cv, count, spec: SpecPVConfig):
    """Append compacted committed KV to the full cache + summaries.

    ck/cv: [L, B, W, Hk, Dh]; count: [B] valid entries (prefix).
    Paged caches scatter the W tokens through the page table and
    recompute only the touched pages' summaries."""
    if "page_table" in cache:
        return _append_paged_cache(cache, ck, cv, count)
    length = cache["length"]

    def write_one(buf, new, off):        # [S,Hk,Dh], [W,Hk,Dh]
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                            (off, 0, 0))

    def write_layer(buf_l, new_l):       # [B,S,Hk,Dh], [B,W,Hk,Dh]
        return jax.vmap(write_one)(buf_l, new_l, length)

    cache = dict(cache)
    cache["k"] = jax.vmap(write_layer)(cache["k"], ck)
    cache["v"] = jax.vmap(write_layer)(cache["v"], cv)
    new_len = length + count
    nkmax, nkmin = jax.vmap(
        lambda kx, kn, kl: update_layer_summaries(kx, kn, kl, length,
                                                  new_len, spec.block_size)
    )(cache["kmax"], cache["kmin"], cache["k"])
    cache["kmax"] = nkmax
    cache["kmin"] = nkmin
    cache["length"] = new_len
    return cache


def _append_paged_cache(cache: Dict, ck, cv, count):
    """Paged commit: per-layer token scatter through the page table plus
    a targeted physical-page summary refresh.  Entries beyond `count`
    are written (and later overwritten) exactly as in the contiguous
    path; rows whose table maps them nowhere land in the null page.

    Precondition (refcounted pages): every block this commit touches —
    ``commit_write_extent`` tokens from ``length`` — is exclusively
    owned by its row.  The engine's pre-step CoW establishes this, so
    the scatter can never write through a page shared with another slot
    or pinned by the prefix cache.  Quest retrieval and the summary
    *reads* need no such guard: shared pages are read-only here."""
    pt = cache["page_table"]
    length = cache["length"]
    w = ck.shape[2]
    blk = cache["k"].shape[2]
    new_len = length + count
    cache = dict(cache)
    cache["k"] = paged_write_layers(cache["k"], pt, length, ck)
    cache["v"] = paged_write_layers(cache["v"], pt, length, cv)
    n_touch = -(-w // blk) + 1
    nkmax, nkmin = jax.vmap(
        lambda kx, kn, pool_l: paged_update_summaries(
            kx, kn, pool_l, pt, length, new_len, n_touch)
    )(cache["kmax"], cache["kmin"], cache["k"])
    cache["kmax"] = nkmax
    cache["kmin"] = nkmin
    cache["length"] = new_len
    return cache


def append_buffer(pkv_k, pkv_v, pkv_pos, body_len: int, buf_len, ck, cv,
                  positions, count):
    """Write committed approximate KV into the pkv buffer region.

    pkv_*: [L, B, Hk, P, Dh]; ck/cv: [L, B, W, Hk, Dh];
    positions: [B, W] absolute positions of committed tokens;
    body_len: static partial-body slot count; buf_len/count: [B]."""
    ckh = jnp.moveaxis(ck, 3, 2)                          # [L, B, Hk, W, Dh]
    cvh = jnp.moveaxis(cv, 3, 2)
    w = ck.shape[2]
    off = body_len + buf_len                              # [B]

    def one(buf, new, o):                                 # [Hk,P,Dh],[Hk,W,Dh]
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                            (0, o, 0))
    def per_layer(buf_l, new_l):
        return jax.vmap(one)(buf_l, new_l, off)

    pkv_k = jax.vmap(per_layer)(pkv_k, ckh)
    pkv_v = jax.vmap(per_layer)(pkv_v, cvh)
    # positions: same for every layer/head; invalid entries -> -1
    posw = jnp.where(jnp.arange(w)[None] < count[:, None], positions, -1)

    def pos_one(buf, new, o):                             # [Hk,P],[Hk,W]
        return jax.lax.dynamic_update_slice(buf, new, (0, o))
    l_, b_, hk = pkv_pos.shape[:3]
    posw_h = jnp.broadcast_to(posw[:, None, :], (b_, hk, w))
    pkv_pos = jax.vmap(lambda buf_l: jax.vmap(pos_one)(buf_l, posw_h, off)
                       )(pkv_pos)
    return pkv_k, pkv_v, pkv_pos, buf_len + count


def refresh_partial_from_queries(cfg: ModelConfig, spec: SpecPVConfig,
                                 queries, q_weight, cache: Dict):
    """Post-commit retrieval refresh: score blocks with this step's queries
    and re-materialise the partial body (sink + retrieval + local).

    queries: [L, B, T, H, Dh]; q_weight: [B, T].
    Returns (pk, pv, ppos): [L, B, Hk, P_body(+pad), Dh].

    Paged caches score from gathered physical-page summaries (a small
    [B, NB, Hk, Dh] gather) and pull the selected blocks straight from
    the pool — Quest retrieval over physical blocks."""
    use_kernel = (spec.use_pallas and spec.score_mode == "paper"
                  and spec.reduction == "mean")
    paged = "page_table" in cache

    def _scores(q_l, kmax_l, kmin_l):
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.retrieval_scores(q_l, kmax_l, kmin_l, q_weight)
        return quest_block_scores(q_l, kmax_l, kmin_l, q_weight,
                                  score_mode=spec.score_mode,
                                  reduction=spec.reduction)

    if paged:
        pt = cache["page_table"]

        def per_layer(q_l, kmax_p, kmin_p, layer):
            scores = _scores(q_l, kmax_p[pt], kmin_p[pt])
            return select_and_gather_partial_paged(
                spec, scores, cache["k"], cache["v"], layer, pt,
                cache["length"])
        # the pool stacks are read in place at each layer's index
        return jax.vmap(per_layer)(queries, cache["kmax"], cache["kmin"],
                                   jnp.arange(queries.shape[0]))

    def per_layer(q_l, kmax_l, kmin_l, k_l, v_l):
        scores = _scores(q_l, kmax_l, kmin_l)
        return select_and_gather_partial(spec, scores, k_l, v_l,
                                         cache["length"])
    return jax.vmap(per_layer)(queries, cache["kmax"], cache["kmin"],
                               cache["k"], cache["v"])


def refresh_partial_blocks(cfg: ModelConfig, spec: SpecPVConfig,
                           queries, q_weight, cache: Dict):
    """Zero-copy refresh: the same Quest scoring + selection as
    ``refresh_partial_from_queries``, but returning the selected
    *logical block ids* instead of gathered bytes — O(budget) index
    writes; the partial body is never materialised.  Paged caches only.

    queries: [L, B, T, H, Dh]; q_weight: [B, T].
    Returns [L, B, Hk, NS] int32 logical block ids with -1 for unused
    selection slots (padded retrieval ranks), matching the validity the
    gathered path encodes via ``pos = -1``."""
    from repro.models.dense import select_partial_blocks
    use_kernel = (spec.use_pallas and spec.score_mode == "paper"
                  and spec.reduction == "mean")
    assert "page_table" in cache, \
        "zero-copy refresh needs the paged cache (contiguous keeps gather)"
    pt = cache["page_table"]

    def _scores(q_l, kmax_l, kmin_l):
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.retrieval_scores(q_l, kmax_l, kmin_l, q_weight)
        return quest_block_scores(q_l, kmax_l, kmin_l, q_weight,
                                  score_mode=spec.score_mode,
                                  reduction=spec.reduction)

    def per_layer(q_l, kmax_p, kmin_p):
        scores = _scores(q_l, kmax_p[pt], kmin_p[pt])
        return select_partial_blocks(spec, scores, cache["length"])

    return jax.vmap(per_layer)(queries, cache["kmax"], cache["kmin"])
