"""Unified model API over all architecture families.

  init_params(cfg, key)                  -> params pytree
  init_cache(cfg, batch, max_len, spec)  -> cache dict (arch-specific keys)
  prefill(cfg, params, tokens, cache)    -> (logits_last, features, cache)
  decode(cfg, params, tokens, positions, cache, ...) -> DecodeOut
  advance(cfg, params, tokens, cache, valid)         -> cache   (ssm/hybrid)
  train_loss(cfg, params, batch, extra)  -> (loss, metrics)

Attention archs (dense/moe/vlm/audio) expose the SpecPV verification
modes through ``decode(mode=...)`` — "full", "partial", and the fused
per-row multi-mode step ("fused", with a ``partial_rows`` row mask);
state archs (ssm/hybrid) expose chain verification (read-only decode)
+ explicit ``advance``.

Sampling needs no model change: verification is a pure logits read, so
greedy acceptance and speculative-sampling acceptance (core/sampling.py)
consume the same ``decode`` outputs — the acceptance rule lives entirely
in the engine.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SpecPVConfig
from repro.models import common as cm
from repro.models import dense as dn
from repro.models import rwkv6 as rw
from repro.models import griffin as gf
from repro.utils import cdiv


class Features(NamedTuple):
    low: jax.Array
    mid: jax.Array
    top: jax.Array

    def fused_input(self):
        """[B, T, 3d] — input to the EAGLE-3 draft fuse layer."""
        return jnp.concatenate([self.low, self.mid, self.top], axis=-1)


class DecodeOut(NamedTuple):
    logits: jax.Array           # [B, T, V] fp32
    features: Optional[Features]
    new_kv: Any                 # (k, v) [L_attn, B, T, Hk, Dh] or None
    partial: Any                # (pk, pv, ppos) or None
    aux_loss: jax.Array
    queries: Any = None         # [L_attn, B, T, H, Dh] when requested


def _is_state_arch(cfg: ModelConfig) -> bool:
    return cfg.arch_type in ("ssm", "hybrid")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key):
    if cfg.arch_type == "ssm":
        return rw.init_params(cfg, key)
    if cfg.arch_type == "hybrid":
        return gf.init_params(cfg, key)
    return dn.init_params(cfg, key)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               spec: Optional[SpecPVConfig] = None, *,
               paged: bool = False,
               num_pages: Optional[int] = None) -> Dict:
    """Cache dict.  ``paged=True`` (attention archs only) replaces the
    per-row [L, B, S_max, ...] layout with a shared block pool
    [L, NumPages, block, Hk*Dh] (lane-dense, ``kvcache/cache.py``) plus
    per-slot page tables — page 0 is the reserved null page, so
    ``num_pages`` defaults to one more than the contiguous capacity
    ``batch * S_max/block``."""
    dtype = cm.dt(cfg.dtype)
    if cfg.arch_type == "ssm":
        assert not paged, "paged KV is attention-only"
        return rw.init_state(cfg, batch, dtype)
    if cfg.arch_type == "hybrid":
        assert not paged, "paged KV is attention-only"
        return gf.init_state(cfg, batch, dtype)
    kinds = cfg.layer_kinds()
    l_attn = dn.attn_layer_count(kinds)
    l_cross = dn.cross_layer_count(kinds)
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    block = spec.block_size if spec else 128
    nb = cdiv(max_len, block)
    if paged:
        from repro.kvcache.cache import init_paged_pool
        np_total = num_pages if num_pages is not None else batch * nb + 1
        cache = init_paged_pool(l_attn, np_total, block, hk, dh, dtype)
        cache["page_table"] = jnp.zeros((batch, nb), jnp.int32)
        cache["length"] = jnp.zeros((batch,), jnp.int32)
    else:
        cache = {
            "k": jnp.zeros((l_attn, batch, max_len, hk, dh), dtype),
            "v": jnp.zeros((l_attn, batch, max_len, hk, dh), dtype),
            "kmax": jnp.zeros((l_attn, batch, nb, hk, dh), jnp.float32),
            "kmin": jnp.zeros((l_attn, batch, nb, hk, dh), jnp.float32),
            "length": jnp.zeros((batch,), jnp.int32),
        }
    if l_cross:
        te = (cfg.num_image_tokens if cfg.arch_type == "vlm"
              else cfg.num_audio_frames)
        cache["cross_k"] = jnp.zeros((l_cross, batch, te, hk, dh), dtype)
        cache["cross_v"] = jnp.zeros((l_cross, batch, te, hk, dh), dtype)
    return cache


# ---------------------------------------------------------------------------
# stub frontends (the one allowed carve-out — see DESIGN.md)
# ---------------------------------------------------------------------------

def extra_inputs_for(cfg: ModelConfig, batch: int, key=None) -> Dict:
    """Pre-computed modality embeddings standing in for the ViT / conv
    frontend.  Deterministic pseudo-features when a key is given."""
    out: Dict[str, jax.Array] = {}
    if cfg.arch_type == "vlm":
        shape = (batch, cfg.num_image_tokens, cfg.vision_dim)
        out["image_embeds"] = (
            jax.random.normal(key, shape, jnp.float32).astype(cm.dt(cfg.dtype))
            if key is not None else jnp.zeros(shape, cm.dt(cfg.dtype)))
    if cfg.has_encoder:
        shape = (batch, cfg.num_audio_frames, cfg.d_model)
        out["frame_embeds"] = (
            jax.random.normal(key, shape, jnp.float32).astype(cm.dt(cfg.dtype))
            if key is not None else jnp.zeros(shape, cm.dt(cfg.dtype)))
    return out


def _encoder_out(cfg: ModelConfig, params, extra):
    if cfg.arch_type == "vlm":
        return dn.project_image(cfg, params, extra["image_embeds"])
    if cfg.has_encoder:
        return dn.encode_frames(cfg, params, extra["frame_embeds"])
    return None


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, cache, *,
            extra: Optional[Dict] = None,
            spec: Optional[SpecPVConfig] = None,
            return_logits: str = "last",
            t_valid=None):
    """Process a chunk of prompt tokens.  Returns (logits, features, cache);
    logits are [B, V] for the last position by default ("last") — computing
    the full [B, T, V] tensor ("all") at 32K x 150K-vocab scale is a
    multi-GiB allocation reserved for tests/teacher-forcing.

    ``t_valid`` ([B] int32, optional; attention archs only) marks the
    chunk ragged: row ``i`` carries ``t_valid[i] >= 1`` real tokens and
    ``t - t_valid[i]`` trailing zero-pads.  Pads are excluded from KV
    writes / summaries / ``length`` advancement, and "last" logits are
    gathered per row at ``t_valid[i] - 1`` — the fused multi-cursor
    prefill step packs cursors of unequal chunk lengths this way."""
    b, t = tokens.shape

    if cfg.arch_type == "ssm":
        assert t_valid is None, "ragged prefill is attention-arch only"
        h, feats, cache = rw.forward(cfg, params, tokens, cache)
        lm = rw.lm_head
    elif cfg.arch_type == "hybrid":
        assert t_valid is None, "ragged prefill is attention-arch only"
        positions = cache["length"][:, None] + jnp.arange(t)[None]
        h, feats, cache = gf.forward(cfg, params, tokens, positions, cache,
                                     mode="advance")
        lm = gf.lm_head
    else:
        positions = cache["length"][:, None] + jnp.arange(t)[None]
        hh = dn.embed_tokens(cfg, params, tokens)
        enc = _encoder_out(cfg, params, extra) if extra else None
        out = dn.trunk_fwd(cfg, params["decoder"], hh, positions,
                           mode="prefill", cache=cache, encoder_out=enc,
                           spec=spec or SpecPVConfig(), t_valid=t_valid)
        h, feats, cache = out.h, out.features, out.cache
        lm = dn.lm_head

    if return_logits == "all":
        logits = lm(cfg, params, h)
    elif t_valid is not None:
        last = jnp.clip(t_valid - 1, 0)[:, None, None]       # [B, 1, 1]
        h_last = jnp.take_along_axis(
            h, jnp.broadcast_to(last, (b, 1, h.shape[-1])), axis=1)
        logits = lm(cfg, params, h_last)[:, 0]
    else:
        logits = lm(cfg, params, h[:, -1:])[:, 0]
    return logits, Features(*feats), cache


# ---------------------------------------------------------------------------
# decode / verify
# ---------------------------------------------------------------------------

def decode(cfg: ModelConfig, params, tokens, positions, cache, *,
           mode: str = "full",
           self_mask=None,
           pkv=None,
           spec: Optional[SpecPVConfig] = None,
           select_partial: bool = False,
           emit_queries: bool = False,
           q_weight=None,
           partial_rows=None,
           pkv_blocks=None) -> DecodeOut:
    """Forward T new (tree/chain) tokens.

    mode: "full" | "partial" | "fused" — attention archs only; state
    archs always do read-only chain verification.  ``"fused"`` is the
    multi-mode verification step: ``partial_rows`` ([B] bool) marks the
    rows that attend the materialised partial cache (``pkv``), every
    other row attends the full cache over its real length — one trunk
    launch serves an arbitrary per-row mode mix.
    self_mask: [B, T, T] bool — tree/chain visibility among the new tokens.
    select_partial: emit a freshly retrieved partial cache (Refresh/init).
    pkv_blocks: [L_attn, B, Hk, NS] int32 — zero-copy partial routing
    (paged caches): partial rows read their selected blocks in place
    through the page table; ``pkv`` then carries the tail buffer only.
    """
    b, t = tokens.shape
    if self_mask is None:
        causal = jnp.tril(jnp.ones((t, t), bool))
        self_mask = jnp.broadcast_to(causal[None], (b, t, t))
    zero_aux = jnp.zeros((), jnp.float32)

    if cfg.arch_type == "ssm":
        h, feats, _ = rw.forward(cfg, params, tokens, cache, update=False)
        return DecodeOut(rw.lm_head(cfg, params, h), Features(*feats),
                         None, None, zero_aux)
    if cfg.arch_type == "hybrid":
        h, feats, _ = gf.forward(cfg, params, tokens, positions, cache,
                                 mode="verify", self_mask=self_mask)
        return DecodeOut(gf.lm_head(cfg, params, h), Features(*feats),
                         None, None, zero_aux)

    h = dn.embed_tokens(cfg, params, tokens)
    trunk_mode = {"full": "decode_full", "partial": "decode_partial",
                  "fused": "decode_fused"}[mode]
    out = dn.trunk_fwd(cfg, params["decoder"], h, positions, mode=trunk_mode,
                       self_mask=self_mask, cache=cache, pkv=pkv,
                       spec=spec or SpecPVConfig(),
                       select_partial=select_partial,
                       emit_queries=emit_queries, q_weight=q_weight,
                       partial_rows=partial_rows, pkv_blocks=pkv_blocks)
    logits = dn.lm_head(cfg, params, out.h)
    return DecodeOut(logits, Features(*out.features), out.new_kv,
                     out.partial, out.aux_loss, out.queries)


def advance(cfg: ModelConfig, params, tokens, cache, valid):
    """State archs: commit accepted tokens (padded; `valid` is a prefix
    mask) into the recurrent state."""
    if cfg.arch_type == "ssm":
        _, _, cache = rw.forward(cfg, params, tokens, cache, valid=valid,
                                 collect_features=False)
        return cache
    if cfg.arch_type == "hybrid":
        positions = cache["length"][:, None] + jnp.cumsum(
            valid.astype(jnp.int32), axis=1) - 1
        positions = jnp.maximum(positions, 0)
        _, _, cache = gf.forward(cfg, params, tokens, positions, cache,
                                 mode="advance", valid=valid,
                                 collect_features=False)
        return cache
    raise ValueError("attention archs commit KV explicitly (repro.core)")


# ---------------------------------------------------------------------------
# training loss
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, valid=None):
    """logits: [B, T, V] fp32; labels: [B, T]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if valid is None:
        return jnp.mean(nll)
    w = valid.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def chunked_lm_loss(cfg: ModelConfig, params, h, labels, *,
                    chunk: int = 512):
    """Final-norm + LM head + cross-entropy computed in sequence chunks so
    the full [B, T, V] logits tensor is never materialised (vocab can be
    150K+); each chunk body is rematerialised in the backward pass."""
    b, t, d = h.shape
    nc = max(1, -(-t // chunk))
    pad = nc * chunk - t
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    valid = (jnp.arange(nc * chunk)[None] < t)
    hs = h.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    vs = valid.reshape(1, nc, chunk).transpose(1, 0, 2)
    scale = params["final_norm"]
    w = params["embed"].T if cfg.tie_embeddings else params["head"]

    def body(carry, xs):
        nll_sum, cnt = carry
        hc, lc, vc = xs
        x = cm.rmsnorm(hc, scale, cfg.norm_eps)
        logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
        logits = cm.constrain_batch(logits, extra_spec=(None, "model"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        wgt = jnp.broadcast_to(vc.astype(jnp.float32), logz.shape)
        nll_sum = nll_sum + jnp.sum((logz - gold) * wgt)
        cnt = cnt + jnp.sum(wgt)
        return (nll_sum, cnt), None

    body = jax.checkpoint(body)
    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, vs))
    return nll / jnp.maximum(cnt, 1.0)


def train_loss(cfg: ModelConfig, params, tokens, *,
               extra: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
    """Next-token LM loss over a [B, S] token batch (plus modality stubs
    for vlm/audio)."""
    b, s = tokens.shape
    inp, lbl = tokens[:, :-1], tokens[:, 1:]
    positions = jnp.broadcast_to(jnp.arange(s - 1)[None], (b, s - 1))

    if cfg.arch_type == "ssm":
        state = rw.init_state(cfg, b, cm.dt(cfg.dtype))
        h, _, _ = rw.forward(cfg, params, inp, state, update=False,
                             collect_features=False)
        loss = chunked_lm_loss(cfg, params, h, lbl)
        return loss, {"lm_loss": loss}
    if cfg.arch_type == "hybrid":
        h, _, _ = gf.forward(cfg, params, inp, positions, None, mode="train",
                             collect_features=False)
        loss = chunked_lm_loss(cfg, params, h, lbl)
        return loss, {"lm_loss": loss}

    h = dn.embed_tokens(cfg, params, inp)
    enc = _encoder_out(cfg, params, extra) if extra else None
    out = dn.trunk_fwd(cfg, params["decoder"], h, positions, mode="train",
                       encoder_out=enc, collect_features=False)
    lm = chunked_lm_loss(cfg, params, out.h, lbl)
    loss = lm + cfg.moe_aux_loss_coef * out.aux_loss
    return loss, {"lm_loss": lm, "aux_loss": out.aux_loss}
