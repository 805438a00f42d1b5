"""Attention-architecture trunk: dense GQA, MoE, VLM (interleaved
cross-attention), and enc-dec audio decoders.

Layers are stored *stacked per superblock slot* and executed with
``lax.scan`` over superblocks so the HLO stays compact for 40-100-layer
configs (compile time O(superblock), not O(L)):

  dense/moe:   superblock = ("attn",)                      x L
  vlm:         superblock = ("attn","attn","attn","attn","cross") x L/5
  whisper dec: superblock = ("dec",)                       x L   (self+cross)

Five forward modes share one scan body:

  train          causal flash attention, no cache
  encode         non-causal flash attention (whisper encoder)
  prefill        write chunk KV into the full cache, attend over it,
                 maintain block summaries (paper eq. (1))
  decode_full    T new (tree) tokens vs full cache + tree self-mask;
                 optionally performs Quest retrieval and emits a gathered
                 partial cache (this is the paper's Full/Refresh step)
  decode_partial T new tokens vs the materialised PartialKV + tree mask
  decode_fused   per-row source select (the fused multi-mode step):
                 rows flagged partial attend the PartialKV, all other
                 rows the full cache at their real length — one launch

Decode modes never mutate the cache: they return the new tokens' per-layer
K/V and (for refresh) the gathered partial segments; the SpecPV engine in
``repro/core`` owns acceptance and cache commits.  That split is what
lets stochastic serving reuse every mode unchanged: sampled rows differ
only in how the engine *reads* the returned logits (rejection sampling
vs argmax), never in what the trunk computes.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SpecPVConfig
from repro.models import common as cm
from repro.models import blocks as bk
from repro.utils import pytree_dataclass, cdiv

# ---------------------------------------------------------------------------
# superblock decomposition
# ---------------------------------------------------------------------------

def superblock_decomp(kinds: Tuple[str, ...]):
    """Smallest period p such that kinds is p-periodic (up to a remainder).
    Returns (pattern, n_super, remainder)."""
    n = len(kinds)
    for p in range(1, n + 1):
        n_super = n // p
        if n_super == 0:
            continue
        ok = all(kinds[i] == kinds[i % p] for i in range(n_super * p))
        if ok and n_super >= 1:
            rem = kinds[n_super * p:]
            # only accept remainders without attention layers (cache layout)
            if not any(k in ("attn", "cross", "dec") for k in rem):
                return kinds[:p], n_super, rem
    return kinds, 1, ()


def attn_layer_count(kinds) -> int:
    return sum(1 for k in kinds if k in ("attn", "dec"))


def cross_layer_count(kinds) -> int:
    return sum(1 for k in kinds if k in ("cross", "dec"))


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key, kind: str) -> Dict:
    pd = cm.dt(cfg.param_dtype)
    ks = cm.split_keys(key, 8)
    p: Dict[str, Any] = {}
    if kind in ("attn", "dec"):
        p["norm1"] = jnp.ones((cfg.d_model,), pd)
        p["attn"] = bk.init_attn_params(cfg, ks[0])
    if kind in ("cross", "dec"):
        p["normx"] = jnp.ones((cfg.d_model,), pd)
        p["xattn"] = bk.init_attn_params(cfg, ks[1])
        if kind == "cross":  # llama-vision style gated cross-attn
            p["norm1"] = jnp.ones((cfg.d_model,), pd)
            p["gate_attn"] = jnp.zeros((), pd)
            p["gate_mlp"] = jnp.zeros((), pd)
    p["norm2"] = jnp.ones((cfg.d_model,), pd)
    if cfg.num_experts and kind in ("attn",):
        p["moe"] = bk.init_moe_params(cfg, ks[2])
    else:
        p["mlp"] = bk.init_mlp_params(cfg, ks[2])
    return p


def init_stack(cfg: ModelConfig, key, kinds: Tuple[str, ...]) -> Dict:
    """Stacked superblock params for a layer stack."""
    pattern, n_super, rem = superblock_decomp(kinds)
    keys = cm.split_keys(key, len(kinds))
    slots: List[Dict] = []
    for j, kind in enumerate(pattern):
        per = [_init_layer(cfg, keys[s * len(pattern) + j], kind)
               for s in range(n_super)]
        slots.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per))
    rem_params = [_init_layer(cfg, keys[n_super * len(pattern) + i], kind)
                  for i, kind in enumerate(rem)]
    return {"slots": slots, "rem": rem_params}


def init_params(cfg: ModelConfig, key) -> Dict:
    pd = cm.dt(cfg.param_dtype)
    ks = cm.split_keys(key, 6)
    params: Dict[str, Any] = {
        "embed": cm.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), pd),
        "final_norm": jnp.ones((cfg.d_model,), pd),
        "decoder": init_stack(cfg, ks[1], cfg.layer_kinds()),
    }
    if not cfg.tie_embeddings:
        params["head"] = cm.dense_init(ks[2], (cfg.d_model, cfg.vocab_size),
                                       dtype=pd)
    if cfg.arch_type == "vlm":
        params["projector"] = cm.dense_init(
            ks[3], (cfg.vision_dim, cfg.d_model), dtype=pd)
    if cfg.has_encoder:
        params["encoder"] = init_stack(cfg, ks[4],
                                       ("attn",) * cfg.encoder_layers)
        params["encoder_norm"] = jnp.ones((cfg.d_model,), pd)
        params["frame_pos"] = cm.embed_init(
            ks[5], (cfg.num_audio_frames, cfg.d_model), pd)
    return params


def embed_tokens(cfg: ModelConfig, params, tokens):
    h = params["embed"][tokens].astype(cm.dt(cfg.dtype))
    return cm.constrain_batch(h)


def lm_head(cfg: ModelConfig, params, h):
    h = cm.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (h @ w.astype(h.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Quest-style retrieval (paper eqs. (1)-(3))
# ---------------------------------------------------------------------------

def quest_block_scores(q, kmax, kmin, q_weight, *, score_mode: str,
                       reduction: str):
    """q: [B, T, H, Dh]; kmax/kmin: [B, NB, Hk, Dh] (fp32);
    q_weight: [B, T] in {0,1} — which queries participate in the reduction.
    Returns scores [B, Hk, NB] (fp32)."""
    b, t, h, dh = q.shape
    nb, hk = kmax.shape[1], kmax.shape[2]
    rep = h // hk
    qg = q.reshape(b, t, hk, rep, dh).astype(jnp.float32)
    if score_mode == "paper":
        # eq. (2): s_{i,j} = max(q_j . Kmax_i, q_j . Kmin_i)
        smax = jnp.einsum("btkrd,bnkd->btkrn", qg, kmax)
        smin = jnp.einsum("btkrd,bnkd->btkrn", qg, kmin)
        s = jnp.maximum(smax, smin)                       # [B,T,Hk,rep,NB]
    else:
        # Quest elementwise upper bound: sum_d max(q_d*Kmax_d, q_d*Kmin_d).
        # kmax: [B,NB,Hk,Dh] -> [B,Hk,NB,Dh]; qg: [B,T,Hk,rep,Dh]
        kx = jnp.moveaxis(kmax, 1, 2)
        kn = jnp.moveaxis(kmin, 1, 2)
        pm = qg[:, :, :, :, None, :] * kx[:, None, :, None, :, :]
        pn = qg[:, :, :, :, None, :] * kn[:, None, :, None, :, :]
        s = jnp.sum(jnp.maximum(pm, pn), axis=-1)         # [B,T,Hk,rep,NB]
    s = jnp.mean(s, axis=3)                               # over grouped heads
    w = q_weight[:, :, None, None].astype(jnp.float32)
    if reduction == "mean":
        s = jnp.sum(s * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1e-9)
    elif reduction == "max":
        s = jnp.max(jnp.where(w > 0, s, -jnp.inf), axis=1)
    elif reduction == "last":
        # index of last valid query per batch
        t_idx = jnp.arange(t)[None, :]
        last = jnp.argmax(jnp.where(q_weight > 0, t_idx, -1), axis=1)  # [B]
        s = jnp.take_along_axis(s, last[:, None, None, None], axis=1)[:, 0]
    else:
        raise ValueError(reduction)
    return s                                              # [B, Hk, NB]


def _select_block_ids(spec: SpecPVConfig, scores, length):
    """Sink + top-K retrieval + local block selection (shared by the
    contiguous and paged gathers).

    scores: [B, Hk, NB]; length: [B].  Returns (idx [B, Hk, NS] logical
    block ids, slot_ok [B, Hk, NS] — False for padded retrieval ranks)."""
    b, hk, nb = scores.shape
    bs = spec.block_size
    n_sink, n_ret, n_loc = (spec.num_sink_blocks, spec.retrieval_budget_blocks,
                            spec.local_window_blocks)

    last_block = (length + bs - 1) // bs                  # [B] exclusive
    loc_lo = jnp.maximum(last_block - n_loc, 0)           # [B]
    blk = jnp.arange(nb)                                  # [NB]
    # retrieval candidates: not sink, not local, inside the filled region
    cand = ((blk[None] >= n_sink) & (blk[None] < loc_lo[:, None]))  # [B,NB]
    masked = jnp.where(cand[:, None, :], scores, -jnp.inf)
    _, ret_idx = jax.lax.top_k(masked, n_ret)             # [B, Hk, n_ret]
    # when there are fewer candidates than n_ret, top_k returns -inf slots;
    # map those to block 0 and invalidate by position masking below
    n_cand = jnp.sum(cand, axis=-1)                       # [B]
    ret_rank_ok = jnp.broadcast_to(
        jnp.arange(n_ret)[None, None] < n_cand[:, None, None],
        (b, hk, n_ret))
    ret_idx = jnp.where(ret_rank_ok, ret_idx, 0)

    sink_idx = jnp.broadcast_to(jnp.arange(n_sink)[None, None],
                                (b, hk, n_sink))
    loc_idx = loc_lo[:, None, None] + jnp.arange(n_loc)[None, None]
    loc_idx = jnp.broadcast_to(loc_idx, (b, hk, n_loc))
    idx = jnp.concatenate([sink_idx, ret_idx, loc_idx], axis=-1)  # [B,Hk,NS]
    slot_ok = jnp.concatenate(
        [jnp.ones((b, hk, n_sink), bool), ret_rank_ok,
         jnp.ones((b, hk, n_loc), bool)], axis=-1)
    return idx, slot_ok


def select_and_gather_partial(spec: SpecPVConfig, scores, k_layer, v_layer,
                              length):
    """Select sink + top-K retrieval + local blocks and gather them.

    scores: [B, Hk, NB]; k_layer/v_layer: [B, S, Hk, Dh]; length: [B].
    Returns (pk, pv, ppos): [B, Hk, P, Dh] x2 and [B, Hk, P] with P =
    spec.partial_budget_tokens.  Invalid slots have pos = -1.
    """
    b, s, hk, dh = k_layer.shape
    bs = spec.block_size
    nb = scores.shape[-1]
    if s < nb * bs:  # cache not block-aligned: pad the gather view
        pad_w = ((0, 0), (0, nb * bs - s), (0, 0), (0, 0))
        k_layer = jnp.pad(k_layer, pad_w)
        v_layer = jnp.pad(v_layer, pad_w)
    idx, slot_ok = _select_block_ids(spec, scores, length)
    ns = idx.shape[-1]

    kb = k_layer[:, : nb * bs].reshape(b, nb, bs, hk, dh)
    kb = kb.transpose(0, 3, 1, 2, 4)                      # [B, Hk, NB, bs, Dh]
    vb = v_layer[:, : nb * bs].reshape(b, nb, bs, hk, dh).transpose(0, 3, 1, 2, 4)
    gi = idx[..., None, None]
    pk = jnp.take_along_axis(kb, jnp.broadcast_to(gi, (b, hk, ns, bs, dh)),
                             axis=2)
    pv = jnp.take_along_axis(vb, jnp.broadcast_to(gi, (b, hk, ns, bs, dh)),
                             axis=2)
    pos = idx[..., None] * bs + jnp.arange(bs)[None, None, None]  # [B,Hk,NS,bs]
    valid = pos < length[:, None, None, None]
    # invalidate slots coming from masked-out retrieval ranks
    valid = valid & slot_ok[..., None]
    pos = jnp.where(valid, pos, -1)
    p = ns * bs
    return (pk.reshape(b, hk, p, dh), pv.reshape(b, hk, p, dh),
            pos.reshape(b, hk, p))


def select_and_gather_partial_paged(spec: SpecPVConfig, scores, pool_k,
                                    pool_v, layer, page_table, length):
    """Paged retrieval: translate the selected logical blocks through the
    page table and gather straight from the shared physical pool — the
    contiguous [B, S, ...] view is never materialised.

    scores: [B, Hk, NB]; pool_k/pool_v: [L, NP, block, Hk*Dh] (the
    stored stack) and ``layer`` the one to read; page_table: [B, NB];
    length: [B].  Same contract as ``select_and_gather_partial``."""
    from repro.kvcache.cache import gather_head_blocks
    bs = pool_k.shape[2]
    b, hk, nb = scores.shape
    dh = pool_k.shape[-1] // hk
    idx, slot_ok = _select_block_ids(spec, scores, length)
    ns = idx.shape[-1]
    idxc = jnp.minimum(idx, nb - 1)
    pg = jnp.take_along_axis(
        jnp.broadcast_to(page_table[:, None], (b, hk, nb)), idxc, axis=2)
    pk = gather_head_blocks(pool_k, layer, pg, dh)        # [B, Hk, NS, bs, Dh]
    pv = gather_head_blocks(pool_v, layer, pg, dh)
    # positions from the *unclamped* logical ids, matching the contiguous
    # gather: an out-of-table id yields pos >= length and masks itself
    pos = idx[..., None] * bs + jnp.arange(bs)[None, None, None]
    valid = (pos < length[:, None, None, None]) & slot_ok[..., None]
    pos = jnp.where(valid, pos, -1)
    p = ns * bs
    return (pk.reshape(b, hk, p, dh), pv.reshape(b, hk, p, dh),
            pos.reshape(b, hk, p))


def select_partial_blocks(spec: SpecPVConfig, scores, length):
    """Zero-copy selection: the block ids a refresh would gather, as
    *indices only*.  Returns [B, Hk, NS] int32 logical block ids with -1
    for unused selection slots (padded retrieval ranks), so the routed
    read path derives its validity purely from ``id >= 0`` and the
    row's committed length — mirroring the gathered baseline's
    ``(pos < length) & slot_ok`` mask exactly."""
    idx, slot_ok = _select_block_ids(spec, scores, length)
    return jnp.where(slot_ok, idx, -1).astype(jnp.int32)


def _routed_partial_context(q, pool_k, pool_v, layer, page_table, pbi,
                            length, pkv_l, use_kernel: bool):
    """Zero-copy partial context partials: the retrieval-selected blocks
    are read *in place* from layer ``layer`` of the pool stack
    ([L, NP, block, Hk*Dh]) through the slot's live page table (``pbi``
    [B, Hk, NS] logical block ids, -1 = unused selection slot), plus the
    small dense tail buffer that absorbs between-refresh appended tokens
    as a second segment.

    Off-kernel (CPU fallback) the two segments are CONCATENATED into
    one per-head dense partial in the gathered baseline's exact slot
    order — same bytes at valid slots (identical clamped-index gather),
    same mask, no float reassociation — so the result is bit-identical
    to attending the materialised partial cache.  The kernel route
    streams the body blocks via
    ``kernels.ops.routed_partial_attention`` and merges the buffer
    partial with exp-rescaling (allclose; TPU or interpret-parity
    tests).  Returns (m, l, acc) fp32 partials."""
    from repro.kvcache.cache import gather_head_blocks
    bs, dh = pool_k.shape[2], q.shape[-1]
    b, nb = page_table.shape
    hk, ns = pbi.shape[1:]
    pk_buf, pv_buf, ppos_buf = pkv_l[:3]
    idxc = jnp.clip(pbi, 0, nb - 1)
    pg = jnp.take_along_axis(
        jnp.broadcast_to(page_table[:, None], (b, hk, nb)), idxc, axis=2)
    if use_kernel:
        from repro.kernels import ops as kops
        vlen = jnp.where(
            pbi >= 0,
            jnp.clip(length[:, None, None] - pbi * bs, 0, bs), 0)
        idx = jnp.where(pbi >= 0, pg, 0)
        part_body = kops.routed_partial_attention(q, pool_k, pool_v, layer,
                                                  idx, vlen)
        part_buf = cm.dense_attn_part_perhead(q, pk_buf, pv_buf,
                                              ppos_buf >= 0)
        return cm.merge_attn_partials([part_body, part_buf])
    kb = gather_head_blocks(pool_k, layer, pg, dh).reshape(b, hk, ns * bs, dh)
    vb = gather_head_blocks(pool_v, layer, pg, dh).reshape(b, hk, ns * bs, dh)
    pos = pbi[..., None] * bs + jnp.arange(bs)[None, None, None]
    valid = ((pbi >= 0)[..., None]
             & (pos < length[:, None, None, None])).reshape(b, hk, ns * bs)
    kcat = jnp.concatenate([kb, pk_buf], axis=2)
    vcat = jnp.concatenate([vb, pv_buf], axis=2)
    vmask = jnp.concatenate([valid, ppos_buf >= 0], axis=2)
    return cm.dense_attn_part_perhead(q, kcat, vcat, vmask)


# ---------------------------------------------------------------------------
# per-layer forward
# ---------------------------------------------------------------------------

def _paged_kernel_ok() -> bool:
    """Backend gate for the Pallas paged decode_full kernel: the
    scalar-prefetch pipeline only pays off on TPU — off-TPU the trunk
    keeps the gathered logical view (tests monkeypatch this to force the
    kernel route through interpret mode)."""
    return jax.default_backend() == "tpu"


def _self_attention(cfg: ModelConfig, mode: str,
                    lp: Dict, h, positions, self_mask, cache_kv, pkv,
                    length, inv_freq, mscale, page_table=None,
                    paged_kernel: bool = False, partial_rows=None,
                    t_valid=None, pkv_blocks=None, layer=None):
    """One self-attention sublayer under the given mode.

    cache_kv: (k_layer, v_layer) for prefill/decode_full/decode_fused
              or None; with page_table set these are read (and, for
              prefill, written) through the table: prefill gets the
              layer's *pool* slices [NP, block, Hk*Dh], the decode
              modes the whole stored stacks [L, NP, block, Hk*Dh],
              read in place at ``layer``.  Tiered residency
              (``kvcache.offload.TierManager``) never changes this
              contract: host-demoted pages are dequantized back into
              the fp pool *in pool dtype* before the step that reads
              them dispatches, and their table entries point at the
              null page while hosted — so every pool read here (and in
              the Pallas paged kernel) stays ordinary fp, with no
              int8 branch in any verify path
    pkv:      (pk, pv, ppos) per-kv-head slots for
              decode_partial/decode_fused or None
    paged_kernel: decode_full/decode_fused + page_table only — stream
              the resident pages through
              ``kernels.ops.paged_verify_attention`` instead of
              materialising the gathered logical view
    partial_rows: [B] bool, decode_fused only — rows whose context is
              the materialised partial cache; all other rows attend the
              full cache over their real length.
    layer:    int32 scalar, paged decode only — this layer's index in
              the pool stacks of ``cache_kv``.
    pkv_blocks: [B, Hk, NS] int32 logical block ids (-1 = unused slot),
              zero-copy partial routing (paged caches only): the
              partial context is read in place from the pool through
              the slot's live page table instead of a materialised
              copy; ``pkv`` then carries only the small tail buffer.
    t_valid:  [B] int32, prefill only — ragged chunk: row i carries
              ``t_valid[i]`` real tokens then zero-pads.  Pad positions
              are excluded from KV writes (paged: routed to the null
              page; contiguous: zero-masked, bit-identical to the
              untouched init zeros a serial schedule leaves) and from
              the attention key mask.  The two context
              partials are computed in one launch and row-selected
              *before* the softmax combine, so each row's result is
              bit-identical to the corresponding single-mode step
              (partial rows see the full cache at effective length 0,
              so neither the gathered view's mask nor the paged
              kernel's ragged page routing streams their pages).
    Returns (attn_out, updates_dict).
    """
    x = cm.rmsnorm(h, lp["norm1"], cfg.norm_eps)
    q = bk.project_q(cfg, lp["attn"], x, positions, inv_freq, mscale)
    k_new, v_new = bk.project_kv(cfg, lp["attn"], x, positions, inv_freq,
                                 mscale)
    b, t = positions.shape
    upd: Dict[str, Any] = {}

    if mode == "train":
        out = cm.flash_attention(q, k_new, v_new, q_positions=positions,
                                 kv_positions=positions, causal=True,
                                 window=cfg.window_size,
                                 chunk=min(512, max(128, t)))
    elif mode == "encode":
        out = cm.flash_attention(q, k_new, v_new, q_positions=positions,
                                 kv_positions=positions, causal=False,
                                 chunk=min(512, max(128, t)))
    elif mode == "prefill":
        t_eff = t_valid if t_valid is not None else t
        valid = (jnp.arange(t)[None] < t_valid[:, None]
                 if t_valid is not None else None)
        if page_table is not None:
            from repro.kvcache.cache import (paged_write_tokens,
                                             gather_page_view)
            pool_k, pool_v = cache_kv[:2]     # [NP, block, Hk*Dh]
            pool_k = paged_write_tokens(pool_k, page_table, length, k_new,
                                        valid)
            pool_v = paged_write_tokens(pool_v, page_table, length, v_new,
                                        valid)
            upd["k_layer"] = pool_k
            upd["v_layer"] = pool_v
            if paged_kernel:
                # blockwise-parallel Pallas prefill: K/V were just
                # written, so the kernel's causal scan over the row's
                # resident pages covers in-chunk self-attention too —
                # the contiguous [B, S, ...] view never materialises
                from repro.kernels import ops as kops
                tv = (t_valid if t_valid is not None
                      else jnp.full((b,), t, jnp.int32))
                out = kops.paged_prefill_attention(
                    q, pool_k[None], pool_v[None], 0, page_table, length,
                    tv)
                return bk.attn_output(cfg, lp["attn"], out), upd, q
            k_layer = gather_page_view(pool_k, page_table, q.shape[-1])
            v_layer = gather_page_view(pool_v, page_table, q.shape[-1])
        else:
            k_layer, v_layer = cache_kv[:2]  # (int8 caches are decode-only)
            from repro.kvcache.cache import append_layer_kv
            if valid is not None:
                k_new_w = jnp.where(valid[..., None, None], k_new, 0)
                v_new_w = jnp.where(valid[..., None, None], v_new, 0)
            else:
                k_new_w, v_new_w = k_new, v_new
            k_layer, v_layer = append_layer_kv(k_layer, v_layer, k_new_w,
                                               v_new_w, length)
            upd["k_layer"] = k_layer
            upd["v_layer"] = v_layer
        s = k_layer.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        kv_valid = kv_pos < (length + t_eff)[:, None]
        out = cm.flash_attention(q, k_layer, v_layer, q_positions=positions,
                                 kv_positions=kv_pos, causal=True,
                                 window=cfg.window_size,
                                 kv_valid=kv_valid, chunk=512)
    elif mode in ("decode_full",):
        if page_table is not None and paged_kernel:
            # stream resident pages HBM->VMEM via the scalar-prefetch
            # kernel; the contiguous [B, S, ...] view never materialises
            from repro.kernels import ops as kops
            part_ctx = kops.paged_verify_attention(
                q, cache_kv[0], cache_kv[1], layer, page_table, length)
            part_self = cm.dense_attn_part(q, k_new, v_new,
                                           mask=self_mask[:, None])
            out = cm.combine_attn_parts([part_ctx, part_self], h.dtype)
            upd["new_k"] = k_new
            upd["new_v"] = v_new
            return bk.attn_output(cfg, lp["attn"], out), upd, q
        if page_table is not None:
            from repro.kvcache.cache import gather_page_view
            k_layer = gather_page_view(cache_kv[0][layer], page_table,
                                       q.shape[-1])
            v_layer = gather_page_view(cache_kv[1][layer], page_table,
                                       q.shape[-1])
            ksc = vsc = None                  # int8 caches stay contiguous
        else:
            k_layer, v_layer = cache_kv[:2]
            ksc, vsc = (cache_kv[2], cache_kv[3]) if len(cache_kv) > 2 \
                else (None, None)
        s = k_layer.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        kv_valid = kv_pos < length[:, None]
        if ksc is not None and t <= 8:
            # int8 + tiny T: fused dense path (scales fold into the dot;
            # avoids the kv-chunk while-loop and its resharding copies)
            part_ctx = cm.dense_attn_part_quant(q, k_layer, v_layer, ksc,
                                                vsc, kv_valid)
        else:
            part_ctx = cm.flash_attention(q, k_layer, v_layer,
                                          q_positions=positions,
                                          kv_positions=kv_pos, causal=True,
                                          kv_valid=kv_valid, chunk=512,
                                          return_partials=True,
                                          k_scale=ksc, v_scale=vsc)
        part_self = cm.dense_attn_part(q, k_new, v_new,
                                       mask=self_mask[:, None])
        out = cm.combine_attn_parts([part_ctx, part_self], h.dtype)
        upd["new_k"] = k_new
        upd["new_v"] = v_new
    elif mode == "decode_partial":
        if pkv_blocks is not None:
            assert page_table is not None and len(pkv) == 3, \
                "zero-copy partial routing needs the paged fp cache"
            part_ctx = _routed_partial_context(
                q, cache_kv[0], cache_kv[1], layer, page_table, pkv_blocks,
                length, pkv, paged_kernel)
        else:
            pk, pv, ppos = pkv[:3]
            pks, pvs = (pkv[3], pkv[4]) if len(pkv) > 3 else (None, None)
            part_ctx = cm.dense_attn_part_perhead(q, pk, pv, ppos >= 0,
                                                  k_scale=pks, v_scale=pvs)
        part_self = cm.dense_attn_part(q, k_new, v_new,
                                       mask=self_mask[:, None])
        out = cm.combine_attn_parts([part_ctx, part_self], h.dtype)
        upd["new_k"] = k_new
        upd["new_v"] = v_new
    elif mode == "decode_fused":
        # one launch, two context sources, row-selected partials: the
        # full-cache part runs at per-row *effective* length (0 for
        # partial rows — the paged kernel's ragged routing then streams
        # none of their pages), the partial-cache part over the pkv
        # slots; each row keeps exactly the partial its mode dictates.
        len_eff = jnp.where(partial_rows, 0, length)
        if page_table is not None and paged_kernel:
            from repro.kernels import ops as kops
            part_full = kops.paged_verify_attention(
                q, cache_kv[0], cache_kv[1], layer, page_table, len_eff)
        else:
            if page_table is not None:
                from repro.kvcache.cache import gather_page_view
                k_layer = gather_page_view(cache_kv[0][layer], page_table,
                                           q.shape[-1])
                v_layer = gather_page_view(cache_kv[1][layer], page_table,
                                           q.shape[-1])
                ksc = vsc = None
            else:
                k_layer, v_layer = cache_kv[:2]
                ksc, vsc = (cache_kv[2], cache_kv[3]) if len(cache_kv) > 2 \
                    else (None, None)
            s = k_layer.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            kv_valid = kv_pos < len_eff[:, None]
            part_full = cm.flash_attention(q, k_layer, v_layer,
                                           q_positions=positions,
                                           kv_positions=kv_pos, causal=True,
                                           kv_valid=kv_valid, chunk=512,
                                           return_partials=True,
                                           k_scale=ksc, v_scale=vsc)
        if pkv_blocks is not None:
            assert page_table is not None and len(pkv) == 3, \
                "zero-copy partial routing needs the paged fp cache"
            part_part = _routed_partial_context(
                q, cache_kv[0], cache_kv[1], layer, page_table, pkv_blocks,
                length, pkv, paged_kernel)
        else:
            pk, pv, ppos = pkv[:3]
            pks, pvs = (pkv[3], pkv[4]) if len(pkv) > 3 else (None, None)
            part_part = cm.dense_attn_part_perhead(q, pk, pv, ppos >= 0,
                                                   k_scale=pks, v_scale=pvs)
        sel = partial_rows[:, None, None]                 # m/l: [B, H, T]
        part_ctx = (jnp.where(sel, part_part[0], part_full[0]),
                    jnp.where(sel, part_part[1], part_full[1]),
                    jnp.where(sel[..., None], part_part[2], part_full[2]))
        part_self = cm.dense_attn_part(q, k_new, v_new,
                                       mask=self_mask[:, None])
        out = cm.combine_attn_parts([part_ctx, part_self], h.dtype)
        upd["new_k"] = k_new
        upd["new_v"] = v_new
    else:
        raise ValueError(mode)

    return bk.attn_output(cfg, lp["attn"], out), upd, q


def _cross_attention(cfg: ModelConfig, lp: Dict, h, cross_kv, inv_freq):
    """Cross-attention over fixed encoder states (no rope on kv slots)."""
    x = cm.rmsnorm(h, lp["normx"], cfg.norm_eps)
    b, t, _ = x.shape
    # queries: no rope (cross-attn is position-free on the kv side)
    q = x @ lp["xattn"]["wq"].astype(x.dtype)
    if "bq" in lp["xattn"]:
        q = q + lp["xattn"]["bq"].astype(x.dtype)
    q = q.reshape(b, t, cfg.num_heads, cfg.head_dim_)
    ck, cv = cross_kv
    if t > 1024:  # train/prefill: tile queries, never a [T, Te] fp32 blob
        te = ck.shape[1]
        zeros = jnp.zeros((b, t), jnp.int32)
        out = cm.flash_attention(q, ck, cv, q_positions=zeros,
                                 kv_positions=jnp.zeros((b, te), jnp.int32),
                                 causal=False, chunk=min(512, te),
                                 q_chunk=512)
    else:
        out = cm.sdpa(q, ck, cv)
    return bk.attn_output(cfg, lp["xattn"], out)


def _mlp_or_moe(cfg: ModelConfig, lp: Dict, h):
    x = cm.rmsnorm(h, lp["norm2"], cfg.norm_eps)
    if "moe" in lp:
        y, aux = bk.moe_fwd(cfg, lp["moe"], x)
        return y, aux
    return bk.mlp_fwd(cfg, lp["mlp"], x), jnp.zeros((), jnp.float32)


def compute_cross_kv(cfg: ModelConfig, lp: Dict, encoder_out):
    """K/V projections of encoder states for one cross layer."""
    b, te, _ = encoder_out.shape
    k = encoder_out @ lp["xattn"]["wk"].astype(encoder_out.dtype)
    v = encoder_out @ lp["xattn"]["wv"].astype(encoder_out.dtype)
    if "bk" in lp["xattn"]:
        k = k + lp["xattn"]["bk"].astype(encoder_out.dtype)
        v = v + lp["xattn"]["bv"].astype(encoder_out.dtype)
    k = k.reshape(b, te, cfg.num_kv_heads, cfg.head_dim_)
    v = v.reshape(b, te, cfg.num_kv_heads, cfg.head_dim_)
    return k, v


# ---------------------------------------------------------------------------
# trunk forward (superblock scan)
# ---------------------------------------------------------------------------

@pytree_dataclass
class TrunkOut:
    h: jax.Array                    # [B, T, d] final hidden (pre-final-norm)
    features: Any                   # (low, mid, top) each [B, T, d] or None
    aux_loss: jax.Array             # scalar fp32 (moe load balance)
    cache: Any                      # updated cache dict (prefill) or None
    new_kv: Any                     # (k, v) [L_attn, B, T, Hk, Dh] or None
    partial: Any                    # (pk, pv, ppos) [L_attn, B, Hk, P, Dh] or None
    queries: Any = None             # [L_attn, B, T, H, Dh] when emit_queries


def _feature_targets(num_layers: int) -> Tuple[int, int, int]:
    """EAGLE-3 taps: low/mid/top decoder hidden states (0-indexed, output
    of layer i)."""
    return (max(0, num_layers // 4), num_layers // 2, num_layers - 1)


def trunk_fwd(cfg: ModelConfig, stack_params: Dict, h, positions, *,
              mode: str,
              self_mask=None,
              cache: Optional[Dict] = None,
              pkv=None,
              encoder_out=None,
              spec: Optional[SpecPVConfig] = None,
              select_partial: bool = False,
              emit_queries: bool = False,
              q_weight=None,
              partial_rows=None,
              kinds: Optional[Tuple[str, ...]] = None,
              collect_features: bool = True,
              t_valid=None,
              pkv_blocks=None):
    """Run the layer stack.  See module docstring for modes.

    cache: dict with "k"/"v": [L_attn,B,S,Hk,Dh], "length": [B],
           "kmax"/"kmin": [L_attn,B,NB,Hk,Dh] (attention archs),
           "cross_k"/"cross_v": [L_cross,B,Te,Hk,Dh] (vlm/audio, decode).
    pkv:   (k, v, pos) arrays [L_attn,B,Hk,P,Dh]/[L_attn,B,Hk,P]
    pkv_blocks: [L_attn, B, Hk, NS] int32 per-layer selected logical
           block ids (zero-copy partial routing, paged decode only) —
           partial context reads route through the page table in place
           and ``pkv`` carries only the tail buffer.
    """
    kinds = kinds if kinds is not None else cfg.layer_kinds()
    pattern, n_super, rem = superblock_decomp(kinds)
    p_len = len(pattern)
    n_attn_per = attn_layer_count(pattern)
    n_cross_per = cross_layer_count(pattern)
    L = len(kinds)
    f_lo, f_mi, f_hi = _feature_targets(L)
    inv_freq = jnp.asarray(cm.rope_inv_freq(cfg))
    mscale = cm.yarn_mscale(cfg)
    b, t = positions.shape
    length = cache["length"] if cache is not None else jnp.zeros((b,), jnp.int32)
    paged = cache is not None and "page_table" in cache
    page_table = cache["page_table"] if paged else None
    routed = (paged and pkv_blocks is not None
              and mode in ("decode_partial", "decode_fused"))
    if not routed:
        pkv_blocks = None
    paged_kernel = (paged and spec is not None
                    and spec.use_pallas and _paged_kernel_ok()
                    and (mode in ("decode_full", "decode_fused")
                         or (mode == "decode_partial" and routed)
                         or (mode == "prefill" and cfg.window_size == 0)))
    t_eff = t_valid if t_valid is not None else t
    if q_weight is None:
        q_weight = jnp.ones((b, t), jnp.float32)

    # zero-copy partial routing reads the pool in place, so a pure
    # partial dispatch needs the cache threaded through the scan too
    needs_cache = mode in ("prefill", "decode_full", "decode_fused") \
        or routed
    decode_mode = mode in ("decode_full", "decode_partial", "decode_fused")
    # paged decode reads the stored pool stacks in place at each layer's
    # index (the kernels' operand IS the stored buffer); scanning them as
    # xs would copy one layer's pool per iteration.  Prefill writes its
    # layer's pool and keeps the per-layer slice.
    pool_stack = paged and decode_mode

    # ---- assemble scan xs --------------------------------------------------
    xs: Dict[str, Any] = {"slot_params": stack_params["slots"]}
    if needs_cache and n_attn_per:
        def rs(a):  # [L_attn, ...] -> [n_super, n_attn_per, ...]
            return a.reshape((n_super, n_attn_per) + a.shape[1:])
        if not pool_stack:
            xs["ck"] = rs(cache["k"])
            xs["cv"] = rs(cache["v"])
        if "k_scale" in cache:   # int8 cache
            xs["cks"] = rs(cache["k_scale"])
            xs["cvs"] = rs(cache["v_scale"])
        if select_partial or mode == "prefill":
            xs["kmax"] = rs(cache["kmax"])
            xs["kmin"] = rs(cache["kmin"])
    if mode in ("decode_partial", "decode_fused") and n_attn_per:
        def rp(a):
            return a.reshape((n_super, n_attn_per) + a.shape[1:])
        xs["pk"], xs["pv"], xs["ppos"] = (rp(pkv[0]), rp(pkv[1]), rp(pkv[2]))
        if len(pkv) > 3:         # int8 partial cache
            xs["pks"], xs["pvs"] = rp(pkv[3]), rp(pkv[4])
        if routed:
            xs["pbi"] = rp(pkv_blocks)
    use_cached_cross = (decode_mode and n_cross_per
                        and cache is not None and "cross_k" in cache)
    if use_cached_cross:
        def rx(a):
            return a.reshape((n_super, n_cross_per) + a.shape[1:])
        xs["xk"] = rx(cache["cross_k"])
        xs["xv"] = rx(cache["cross_v"])
    xs["sidx"] = jnp.arange(n_super)

    # ---- scan body ---------------------------------------------------------
    train_like = mode in ("train", "encode")

    def _train_layer(kind):
        """Stateless per-layer step for train/encode (checkpointable)."""
        def apply(hh, lp):
            aux_l = jnp.zeros((), jnp.float32)
            if kind in ("attn", "dec"):
                att, _, _ = _self_attention(cfg, mode, lp, hh, positions,
                                            self_mask, None, None, length,
                                            inv_freq, mscale)
                hh = hh + att
            if kind in ("cross", "dec"):
                cross_kv = compute_cross_kv(cfg, lp, encoder_out)
                xo = _cross_attention(cfg, lp, hh, cross_kv, inv_freq)
                if kind == "cross":
                    xo = jnp.tanh(lp["gate_attn"].astype(jnp.float32)
                                  ).astype(hh.dtype) * xo
                hh = hh + xo
            m, aux_l2 = _mlp_or_moe(cfg, lp, hh)
            if kind == "cross":
                m = jnp.tanh(lp["gate_mlp"].astype(jnp.float32)
                             ).astype(hh.dtype) * m
            hh = cm.constrain_batch(hh + m, extra_spec=("model",))
            return hh, aux_l + aux_l2
        return apply

    def body(carry, x):
        if collect_features:
            h, flo, fmi, fhi, aux = carry
        else:
            h, aux = carry
            flo = fmi = fhi = None
        a_i = 0   # attn-layer index within superblock
        c_i = 0   # cross-layer index within superblock
        ys: Dict[str, List] = {k: [] for k in
                               ("nk", "nv", "uk", "uv", "ukmax", "ukmin",
                                "ppk", "ppv", "pppos", "cxk", "cxv", "q")}
        if train_like and cfg.remat and len(pattern) > 1:
            # per-layer rematerialisation inside multi-layer superblocks
            for j, kind in enumerate(pattern):
                lp = x["slot_params"][j]
                step_fn = jax.checkpoint(_train_layer(kind))
                h, aux_l = step_fn(h, lp)
                aux = aux + aux_l
                if collect_features:
                    g = x["sidx"] * p_len + j
                    flo = jnp.where(g == f_lo, h, flo)
                    fmi = jnp.where(g == f_mi, h, fmi)
                    fhi = jnp.where(g == f_hi, h, fhi)
            out_carry = ((h, flo, fmi, fhi, aux) if collect_features
                         else (h, aux))
            return out_carry, {}
        for j, kind in enumerate(pattern):
            lp = x["slot_params"][j]
            if kind in ("attn", "dec"):
                layer = None
                if needs_cache and pool_stack:
                    cache_kv = (cache["k"], cache["v"])
                    layer = x["sidx"] * n_attn_per + a_i
                elif needs_cache:
                    cache_kv = (x["ck"][a_i], x["cv"][a_i])
                    if "cks" in x:
                        cache_kv += (x["cks"][a_i], x["cvs"][a_i])
                else:
                    cache_kv = None
                if mode in ("decode_partial", "decode_fused"):
                    pkv_l = (x["pk"][a_i], x["pv"][a_i], x["ppos"][a_i])
                    if "pks" in x:
                        pkv_l += (x["pks"][a_i], x["pvs"][a_i])
                else:
                    pkv_l = None
                att, upd, q = _self_attention(
                    cfg, mode, lp, h, positions, self_mask, cache_kv, pkv_l,
                    length, inv_freq, mscale, page_table=page_table,
                    paged_kernel=paged_kernel, partial_rows=partial_rows,
                    t_valid=t_valid,
                    pkv_blocks=(x["pbi"][a_i] if "pbi" in x else None),
                    layer=layer)
                h = h + att
                if mode == "prefill":
                    if paged:
                        from repro.kvcache.cache import paged_update_summaries
                        blk = upd["k_layer"].shape[1]
                        nkmax, nkmin = paged_update_summaries(
                            x["kmax"][a_i], x["kmin"][a_i], upd["k_layer"],
                            page_table, length, length + t_eff,
                            n_touch=cdiv(t, blk) + 1)
                    else:
                        from repro.kvcache.cache import update_layer_summaries
                        nkmax, nkmin = update_layer_summaries(
                            x["kmax"][a_i], x["kmin"][a_i], upd["k_layer"],
                            length, length + t_eff, spec.block_size)
                    ys["uk"].append(upd["k_layer"])
                    ys["uv"].append(upd["v_layer"])
                    ys["ukmax"].append(nkmax)
                    ys["ukmin"].append(nkmin)
                if decode_mode:
                    ys["nk"].append(upd["new_k"])
                    ys["nv"].append(upd["new_v"])
                if emit_queries:
                    ys["q"].append(q)
                if select_partial:
                    if paged:
                        kmax_log = x["kmax"][a_i][page_table]  # [B,NB,Hk,Dh]
                        kmin_log = x["kmin"][a_i][page_table]
                    else:
                        kmax_log = x["kmax"][a_i]
                        kmin_log = x["kmin"][a_i]
                    scores = quest_block_scores(
                        q, kmax_log, kmin_log, q_weight,
                        score_mode=spec.score_mode, reduction=spec.reduction)
                    if paged:
                        ppk, ppv, pppos = select_and_gather_partial_paged(
                            spec, scores, cache["k"], cache["v"], layer,
                            page_table, length)
                    else:
                        ppk, ppv, pppos = select_and_gather_partial(
                            spec, scores, x["ck"][a_i], x["cv"][a_i], length)
                    ys["ppk"].append(ppk)
                    ys["ppv"].append(ppv)
                    ys["pppos"].append(pppos)
                a_i += 1
            if kind in ("cross", "dec"):
                if use_cached_cross:
                    cross_kv = (x["xk"][c_i], x["xv"][c_i])
                else:
                    cross_kv = compute_cross_kv(cfg, lp, encoder_out)
                    if mode == "prefill":
                        ys["cxk"].append(cross_kv[0])
                        ys["cxv"].append(cross_kv[1])
                xo = _cross_attention(cfg, lp, h, cross_kv, inv_freq)
                if kind == "cross":
                    xo = jnp.tanh(lp["gate_attn"].astype(jnp.float32)
                                  ).astype(h.dtype) * xo
                h = h + xo
                c_i += 1
            if kind == "rec":
                raise AssertionError("rec layers belong to griffin trunk")
            m, aux_l = _mlp_or_moe(cfg, lp, h)
            if kind == "cross":
                m = jnp.tanh(lp["gate_mlp"].astype(jnp.float32)
                             ).astype(h.dtype) * m
            # batch over data axes; in train/prefill/encode additionally
            # shard the sequence over `model` (sequence parallelism) —
            # silently dropped when T doesn't divide (decode trees)
            seq_ax = "model" if mode in ("train", "prefill", "encode") \
                else None
            h = cm.constrain_batch(h + m, extra_spec=(seq_ax,))
            aux = aux + aux_l
            if collect_features:
                g = x["sidx"] * p_len + j
                flo = jnp.where(g == f_lo, h, flo)
                fmi = jnp.where(g == f_mi, h, fmi)
                fhi = jnp.where(g == f_hi, h, fhi)
        ys_arr = {k: (jnp.stack(v) if len(v) > 1 else v[0][None])
                  for k, v in ys.items() if v}
        out_carry = ((h, flo, fmi, fhi, aux) if collect_features
                     else (h, aux))
        return out_carry, ys_arr

    z = jnp.zeros_like(h)
    aux0 = jnp.zeros((), jnp.float32)
    carry0 = (h, z, z, z, aux0) if collect_features else (h, aux0)
    if mode in ("train", "encode") and cfg.remat:
        body = jax.checkpoint(body)
    if collect_features:
        (h, flo, fmi, fhi, aux), ys = jax.lax.scan(body, carry0, xs)
    else:
        (h, aux), ys = jax.lax.scan(body, carry0, xs)
        flo = fmi = fhi = None

    # ---- remainder layers (no attention by construction) -------------------
    for i, kind in enumerate(rem):
        lp = stack_params["rem"][i]
        m, aux_l = _mlp_or_moe(cfg, lp, h)
        h = h + m
        aux = aux + aux_l
        g = n_super * p_len + i
        if collect_features:
            if g == f_lo:
                flo = h
            if g == f_mi:
                fmi = h
            if g == f_hi:
                fhi = h

    def flat(name):  # [n_super, n_per, ...] -> [L, ...]
        a = ys[name]
        return a.reshape((-1,) + a.shape[2:])

    new_cache = None
    if mode == "prefill":
        new_cache = dict(cache)
        new_cache["k"] = flat("uk")
        new_cache["v"] = flat("uv")
        new_cache["kmax"] = flat("ukmax")
        new_cache["kmin"] = flat("ukmin")
        new_cache["length"] = length + t_eff
        if "cxk" in ys:
            new_cache["cross_k"] = flat("cxk")
            new_cache["cross_v"] = flat("cxv")
    new_kv = ((flat("nk"), flat("nv")) if decode_mode and "nk" in ys else None)
    partial = ((flat("ppk"), flat("ppv"), flat("pppos"))
               if select_partial and "ppk" in ys else None)
    queries = flat("q") if emit_queries and "q" in ys else None
    feats = (flo, fmi, fhi) if collect_features else None
    return TrunkOut(h=h, features=feats, aux_loss=aux, cache=new_cache,
                    new_kv=new_kv, partial=partial, queries=queries)


def encode_frames(cfg: ModelConfig, params, frame_embeds):
    """Whisper encoder: frame embeddings [B, Te, d] -> encoder states."""
    h = frame_embeds.astype(cm.dt(cfg.dtype))
    h = h + params["frame_pos"][None, : h.shape[1]].astype(h.dtype)
    b, te, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(te)[None], (b, te))
    out = trunk_fwd(cfg, params["encoder"], h, pos, mode="encode",
                    kinds=("attn",) * cfg.encoder_layers,
                    collect_features=False)
    return cm.rmsnorm(out.h, params["encoder_norm"], cfg.norm_eps)


def project_image(cfg: ModelConfig, params, image_embeds):
    """VLM projector: [B, Timg, vision_dim] -> [B, Timg, d_model]."""
    x = image_embeds.astype(cm.dt(cfg.dtype))
    return x @ params["projector"].astype(x.dtype)
