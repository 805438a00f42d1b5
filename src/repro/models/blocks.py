"""Per-layer blocks: attention projections, dense MLP, MoE (capacity-based
dispatch a la MaxText — keeps compiled FLOPs proportional to *active*
experts and shards cleanly over the `model` mesh axis on the expert dim).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm

# ---------------------------------------------------------------------------
# attention projections
# ---------------------------------------------------------------------------

def init_attn_params(cfg: ModelConfig, key, *, cross: bool = False,
                     kv_in_dim: int = 0) -> Dict:
    pd = cm.dt(cfg.param_dtype)
    d, h, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    kin = kv_in_dim or d
    ks = cm.split_keys(key, 4)
    p = {
        "wq": cm.dense_init(ks[0], (d, h * dh), dtype=pd),
        "wk": cm.dense_init(ks[1], (kin, hk * dh), dtype=pd),
        "wv": cm.dense_init(ks[2], (kin, hk * dh), dtype=pd),
        "wo": cm.dense_init(ks[3], (h * dh, d), dtype=pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), pd)
        p["bk"] = jnp.zeros((hk * dh,), pd)
        p["bv"] = jnp.zeros((hk * dh,), pd)
    return p


def _head_norm(cfg: ModelConfig, x, scale):
    """Qwen3's per-head RMSNorm over head_dim (``q_norm`` / ``k_norm``),
    float32 inside, the activation dtype out."""
    with jax.named_scope("qk_norm"):
        return cm.rmsnorm(x, scale, cfg.norm_eps)


def project_q(cfg: ModelConfig, p: Dict, x, positions, inv_freq, mscale):
    """x: [B, T, d] -> roped q: [B, T, H, Dh].  When ``p`` holds
    ``q_norm`` ([Dh], Qwen3), each head is RMS-normalised before RoPE."""
    b, t, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(b, t, cfg.num_heads, cfg.head_dim_)
    if "q_norm" in p:
        q = _head_norm(cfg, q, p["q_norm"])
    return cm.apply_rope(q, positions, inv_freq, mscale)


def project_kv(cfg: ModelConfig, p: Dict, x, positions, inv_freq, mscale,
               *, rope: bool = True):
    """x: [B, T, d(or kv_in)] -> (k, v): [B, T, Hk, Dh]; k is roped so the KV
    cache stores position-encoded keys (gatherable without re-roping).
    When ``p`` holds ``k_norm`` ([Dh], Qwen3), each key head is
    RMS-normalised before RoPE, so the cache, and the block summaries
    retrieval scores, hold normalised keys."""
    b, t, _ = x.shape
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(b, t, cfg.num_kv_heads, cfg.head_dim_)
    v = v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim_)
    if "k_norm" in p:
        k = _head_norm(cfg, k, p["k_norm"])
    if rope:
        k = cm.apply_rope(k, positions, inv_freq, mscale)
    return k, v


def attn_output(cfg: ModelConfig, p: Dict, attn):
    """attn: [B, T, H, Dh] -> [B, T, d]"""
    b, t, h, dh = attn.shape
    return attn.reshape(b, t, h * dh) @ p["wo"].astype(attn.dtype)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU / GeGLU / plain)
# ---------------------------------------------------------------------------

def init_mlp_params(cfg: ModelConfig, key) -> Dict:
    pd = cm.dt(cfg.param_dtype)
    d, f = cfg.d_model, cfg.d_ff
    ks = cm.split_keys(key, 3)
    if cfg.act in ("silu", "gelu"):  # gated
        return {"wi": cm.dense_init(ks[0], (d, f), dtype=pd),
                "wg": cm.dense_init(ks[1], (d, f), dtype=pd),
                "wo": cm.dense_init(ks[2], (f, d), dtype=pd)}
    return {"wi": cm.dense_init(ks[0], (d, f), dtype=pd),
            "wo": cm.dense_init(ks[2], (f, d), dtype=pd)}


def mlp_fwd(cfg: ModelConfig, p: Dict, x):
    act = cm.act_fn(cfg.act)
    if "wg" in p:
        h = act(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    else:
        h = act(x @ p["wi"].astype(x.dtype))
    return h @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (top-k router, capacity-based dispatch)
# ---------------------------------------------------------------------------

def init_moe_params(cfg: ModelConfig, key) -> Dict:
    pd = cm.dt(cfg.param_dtype)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = cm.split_keys(key, 4)

    def einit(k, shape):
        kk = jax.random.split(k, e)
        return jnp.stack([cm.dense_init(kk[i], shape, dtype=pd)
                          for i in range(e)])

    return {
        "router": cm.dense_init(ks[0], (d, e), dtype=jnp.float32),
        "wi": einit(ks[1], (d, f)),      # [E, d, f]
        "wg": einit(ks[2], (d, f)),
        "wo": einit(ks[3], (f, d)),
    }


MOE_GROUP = 1024  # tokens per dispatch group (bounds the [g, E, C] tensors)


def _moe_group_fwd(cfg: ModelConfig, p: Dict, xf, *, capacity_factor: float):
    """One dispatch group.  xf: [g, d] -> (y [g, d], aux scalar)."""
    g, d = xf.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = (xf.astype(jnp.float32) @ p["router"])          # [g, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                     # [g, K]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(math.ceil(g * k / e * capacity_factor)))
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)      # [g, K, E]
    pos_in_e = (jnp.cumsum(onehot.reshape(g * k, e), axis=0) - 1.0)
    pos_in_e = pos_in_e.reshape(g, k, e)
    keep = (pos_in_e < cap) & (onehot > 0)                   # drop overflow
    pos = jnp.clip(pos_in_e, 0, cap - 1).astype(jnp.int32)
    # accumulate dispatch/combine per top-k slot: peak tensor is [g, E, C]
    # (never [g, K, E, C])
    dispatch = jnp.zeros((g, e, cap), jnp.float32)
    combine = jnp.zeros((g, e, cap), jnp.float32)
    for kk in range(k):
        sel = (jax.nn.one_hot(pos[:, kk, :], cap, dtype=jnp.float32)
               * keep[:, kk, :, None])                       # [g, E, C]
        dispatch = dispatch + sel
        combine = combine + sel * topv[:, kk, None, None].astype(jnp.float32)

    xd = xf.dtype
    xe = jnp.einsum("nd,nec->ecd", xf, dispatch.astype(xd))  # [E, C, d]
    act = cm.act_fn(cfg.act)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(xd)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(xd))
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(xd))   # [E, C, d]
    y = jnp.einsum("ecd,nec->nd", ye, combine.astype(xd))

    frac_tokens = jnp.mean(onehot[:, 0, :], axis=0)          # top-1 assignment
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux.astype(jnp.float32)


MOE_MAX_OUTER = 64  # sequential dispatch waves for very long token streams


def moe_fwd(cfg: ModelConfig, p: Dict, x, *, capacity_factor: float = 1.25
            ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, T, d] -> (y, aux_loss).

    Capacity-based dispatch/combine via one-hot einsums (the TPU-friendly
    MaxText formulation, expert dim sharded over `model`).  Tokens are
    dispatched in groups of MOE_GROUP so the [g, E, C] tensors stay bounded
    (C grows with group size): groups run data-parallel under vmap, with an
    outer scan capped at MOE_MAX_OUTER waves for very long streams."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    if n <= MOE_GROUP:
        y, aux = _moe_group_fwd(cfg, p, xf,
                                capacity_factor=capacity_factor)
        return y.reshape(b, t, d), aux
    g = MOE_GROUP
    ng = -(-n // g)
    outer = min(ng, MOE_MAX_OUTER)
    ng = -(-ng // outer) * outer
    pad = ng * g - n
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    inner = ng // outer
    xg = xf.reshape(outer, inner, g, d)

    grp = jax.vmap(functools.partial(_moe_group_fwd, cfg, p,
                                     capacity_factor=capacity_factor))

    def body(_, xc):                    # xc: [inner, g, d]
        y, aux = grp(xc)
        return (), (y, aux)

    # recompute each dispatch wave in the backward pass — the one-hot
    # dispatch/combine tensors are far larger than the wave's inputs
    body = jax.checkpoint(body)
    _, (yg, auxg) = jax.lax.scan(body, (), xg)
    y = yg.reshape(ng * g, d)[:n]
    return y.reshape(b, t, d), jnp.mean(auxg)
