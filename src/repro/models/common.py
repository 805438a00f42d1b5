"""Shared building blocks: inits, norms, RoPE (+YARN), activations,
and a memory-bounded chunked ("flash-style") attention in pure JAX.

Everything is functional: params are nested dicts of jnp arrays.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# dtype helpers / init
# ---------------------------------------------------------------------------

def dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LLM practice)."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def groupnorm_heads(x, scale, bias, eps=1e-5):
    """Per-head groupnorm used by RWKV time-mix output.  x: [..., H, Dh]."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if name == "relu_sq":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------------
# RoPE with optional YARN (NTK-by-parts) scaling
# ---------------------------------------------------------------------------

def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies, with YARN NTK-by-parts interpolation when
    cfg.yarn_factor > 1 (Peng et al., 2023 — used by the paper to extend the
    EAGLE-3 draft module to 64K)."""
    dim = cfg.head_dim_
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    s = cfg.yarn_factor
    if s > 1.0:
        beta_fast, beta_slow = 32.0, 1.0
        L = cfg.yarn_orig_len

        def corr_dim(n_rot):
            return (dim * math.log(L / (n_rot * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
        idx = np.arange(dim // 2, dtype=np.float64)
        ramp = np.clip((idx - low) / max(high - low, 1e-3), 0.0, 1.0)
        # ramp=0 -> high freq (no interpolation); ramp=1 -> full interpolation
        inv = inv * (1 - ramp) + (inv / s) * ramp
    return inv.astype(np.float32)


def yarn_mscale(cfg: ModelConfig) -> float:
    s = cfg.yarn_factor
    if s <= 1.0:
        return 1.0
    return 0.1 * math.log(s) + 1.0


def apply_rope(x, positions, inv_freq, mscale: float = 1.0):
    """x: [..., T, H, Dh]; positions: broadcastable to [..., T]."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, Dh/2]
    sin = jnp.sin(ang)[..., None, :] * mscale
    cos = jnp.cos(ang)[..., None, :] * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention math (pure-JAX paths)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def cdiv_(a: int, b: int) -> int:
    return -(-a // b)


def current_mesh():
    """The ambient mesh (``jax.set_mesh``), or None outside any mesh
    context.  Callers use its ``axis_names`` and ``shape`` mapping."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def constrain_batch(x, extra_spec=()):
    """Constrain the leading (batch) dim of an activation onto the data
    axes of the ambient mesh, plus optional per-dim extra axes (each
    silently dropped when the dim doesn't divide or the axis is absent).
    A no-op when no mesh is set (single-device CPU paths)."""
    from jax.sharding import PartitionSpec as P
    m = current_mesh()
    if m is None or not m.axis_names:
        return x

    def ok(dim: int, axes) -> bool:
        if axes is None:
            return True
        ax = (axes,) if isinstance(axes, str) else tuple(axes)
        if not all(a in m.axis_names for a in ax):
            return False
        size = 1
        for a in ax:
            size *= m.shape[a]
        return dim % size == 0 and dim >= size

    axes = tuple(a for a in ("pod", "data") if a in m.axis_names)
    if not axes:
        return x
    if not ok(x.shape[0], axes):
        axes = tuple(a for a in ("data",) if a in m.axis_names)
        if not axes or not ok(x.shape[0], axes):
            axes = None
    rest = []
    for i, a in enumerate(extra_spec, start=1):
        rest.append(a if (i < len(x.shape) and ok(x.shape[i], a)) else None)
    # pad remaining dims with None
    rest += [None] * (len(x.shape) - 1 - len(rest))
    if axes is None and all(r is None for r in rest):
        return x
    return jax.lax.with_sharding_constraint(x, P(axes, *rest))


def ckpt_chunked_scan(step, init, xs, *, chunk: int = 256):
    """lax.scan over the leading (time) axis with gradient checkpointing at
    segment boundaries: states are saved every `chunk` steps and segments
    are recomputed in the backward pass — O(T/chunk + chunk) live state
    instead of O(T) for recurrences (RWKV wkv, RG-LRU).

    Padding tail steps must be no-ops in `step` (gate on a validity input).
    """
    leaves = jax.tree_util.tree_leaves(xs)
    t = leaves[0].shape[0]
    if t <= chunk:
        return jax.lax.scan(step, init, xs)
    nseg = -(-t // chunk)
    pad = nseg * chunk - t

    def pad_leaf(a):
        if not pad:
            return a
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths)

    xs_p = jax.tree_util.tree_map(
        lambda a: pad_leaf(a).reshape((nseg, chunk) + a.shape[1:]), xs)

    def seg_body(carry, xseg):
        return jax.lax.scan(step, carry, xseg)

    seg_body = jax.checkpoint(seg_body)
    carry, ys = jax.lax.scan(seg_body, init, xs_p)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((nseg * chunk,) + a.shape[2:])[:t], ys)
    return carry, ys


def repeat_kv(k, n_rep: int):
    """[B, S, Hk, Dh] -> [B, S, Hk*n_rep, Dh]"""
    if n_rep == 1:
        return k
    b, s, hk, dh = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hk, n_rep, dh))
    return k.reshape(b, s, hk * n_rep, dh)


def sdpa(q, k, v, mask=None, scale: Optional[float] = None):
    """Dense scaled-dot-product attention (reference / small-context path).

    q: [B, T, H, Dh]; k/v: [B, S, Hk, Dh]; mask: [B, 1|H, T, S] bool or None.
    """
    b, t, h, dh = q.shape
    hk = k.shape[2]
    k = repeat_kv(k, h // hk)
    v = repeat_kv(v, h // hk)
    scale = scale or (1.0 / math.sqrt(dh))
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)
    return out


def flash_attention(q, k, v, *, q_positions, kv_positions,
                    causal: bool = True, window: int = 0,
                    kv_valid=None, chunk: int = 512,
                    scale: Optional[float] = None,
                    return_partials: bool = False,
                    q_chunk: int = 512,
                    k_scale=None, v_scale=None):
    """Memory-bounded chunked attention in pure JAX: an outer lax.scan over
    query tiles and an inner lax.scan over KV tiles with a running
    (m, l, acc) — the classic flash recurrence.  Peak live attention tensor
    is [B, H, q_chunk, chunk] regardless of T and S, which is what keeps
    the 32K-prefill / 4K-train dry-runs inside HBM.

    q:  [B, T, H, Dh]     q_positions:  [B, T] absolute positions
    k,v:[B, S, Hk, Dh]    kv_positions: [B, S]
    window > 0 limits attention to kv_pos > q_pos - window (sliding window).
    kv_valid: [B, S] bool — invalid positions are masked out.
    """
    b, t = q.shape[:2]
    if t > q_chunk and not return_partials:
        nq = cdiv_(t, q_chunk)
        pad_t = nq * q_chunk - t
        if pad_t:
            q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
            q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_t)),
                                  constant_values=jnp.iinfo(jnp.int32).max
                                  if causal else 0)
        qs = q.reshape(b, nq, q_chunk, *q.shape[2:]).transpose(1, 0, 2, 3, 4)
        ps = q_positions.reshape(b, nq, q_chunk).transpose(1, 0, 2)

        def qbody(_, xs):
            qc, pc = xs
            out = flash_attention(qc, k, v, q_positions=pc,
                                  kv_positions=kv_positions, causal=causal,
                                  window=window, kv_valid=kv_valid,
                                  chunk=chunk, scale=scale,
                                  q_chunk=q_chunk, k_scale=k_scale,
                                  v_scale=v_scale)
            return (), out

        qbody = jax.checkpoint(qbody)
        _, outs = jax.lax.scan(qbody, (), (qs, ps))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk,
                                                    *q.shape[2:])
        return out[:, :t]
    b, t, h, dh = q.shape
    s = k.shape[1]
    hk = k.shape[2]
    n_rep = h // hk
    scale = scale or (1.0 / math.sqrt(dh))

    pad = (-s) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=jnp.iinfo(jnp.int32).max)
        if kv_valid is None:
            kv_valid = jnp.broadcast_to(jnp.arange(s + pad)[None, :] < s,
                                        (b, s + pad))
        else:
            kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    elif kv_valid is None:
        kv_valid = jnp.ones((b, s), dtype=bool)

    n_chunks = (s + pad) // chunk
    ks = k.reshape(b, n_chunks, chunk, hk, dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_chunks, chunk, hk, dh).transpose(1, 0, 2, 3, 4)
    ps = kv_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    vals = kv_valid.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    if k_scale is not None:
        kss = k_scale.reshape(b, n_chunks, chunk, hk).transpose(1, 0, 2, 3)
        vss = v_scale.reshape(b, n_chunks, chunk, hk).transpose(1, 0, 2, 3)
    else:
        kss = vss = jnp.zeros((n_chunks, b, 0, hk), jnp.bfloat16)

    qf = (q * scale).astype(jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, pc, vc_valid, ksc, vsc = xs
        if k_scale is not None:  # int8 KV: dequantize this tile only
            kc = (kc.astype(jnp.float32)
                  * ksc.astype(jnp.float32)[..., None]).astype(jnp.bfloat16)
            vc = (vc.astype(jnp.float32)
                  * vsc.astype(jnp.float32)[..., None]).astype(jnp.bfloat16)
        kr = repeat_kv(kc, n_rep)  # [B, c, H, Dh]
        logits = jnp.einsum("bthd,bshd->bhts", qf,
                            kr.astype(jnp.float32))  # [B, H, T, c]
        ok = vc_valid[:, None, None, :]
        if causal:
            ok = ok & (pc[:, None, None, :] <= q_positions[:, None, :, None])
        if window > 0:
            ok = ok & (pc[:, None, None, :]
                       > q_positions[:, None, :, None] - window)
        logits = jnp.where(ok, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: if every key so far is masked, m_new == NEG_INF and the
        # naive exp() would give p == 1 for masked slots — zero them out.
        p = jnp.exp(logits - m_new[..., None]) * ok
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        vr = repeat_kv(vc, n_rep).astype(jnp.float32)
        acc_new = acc * corr[..., None] + jnp.einsum("bhts,bshd->bthd",
                                                     p, vr).transpose(0, 2, 1, 3)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    a0 = jnp.zeros((b, h, t, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (ks, vs, ps, vals, kss, vss))
    if return_partials:
        return (m, l, acc)
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # [B, H, T, Dh]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # [B, T, H, Dh]


# ---------------------------------------------------------------------------
# attention "partials" — (m, l, acc) triples that can be combined across
# independent context segments (full-cache part + tree part, partial-cache
# part + tree part, ...).  All fp32; m/l: [B, H, T]; acc: [B, H, T, Dh].
# ---------------------------------------------------------------------------

def dense_attn_part(q, k, v, *, mask=None, scale=None):
    """q: [B, T, H, Dh]; k/v: [B, S, Hk, Dh]; mask: broadcastable
    [B, 1|H, T, S] bool.  Returns (m, l, acc)."""
    b, t, h, dh = q.shape
    hk = k.shape[2]
    kr = repeat_kv(k, h // hk).astype(jnp.float32)
    vr = repeat_kv(v, h // hk).astype(jnp.float32)
    scale = scale or (1.0 / math.sqrt(dh))
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale, kr)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    if mask is not None:
        p = p * mask  # all-masked rows would otherwise get p == 1
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhts,bshd->bhtd", p, vr)
    return m, l, acc


def dense_attn_part_perhead(q, kph, vph, valid, *, scale=None,
                            k_scale=None, v_scale=None):
    """Per-kv-head context slots (the materialised partial cache).

    q: [B, T, H, Dh]; kph/vph: [B, Hk, P, Dh]; valid: [B, Hk, P] bool.
    Optional int8 slots with k_scale/v_scale: [B, Hk, P].
    """
    b, t, h, dh = q.shape
    hk = kph.shape[1]
    rep = h // hk
    scale = scale or (1.0 / math.sqrt(dh))
    if k_scale is not None:
        kph = kph.astype(jnp.float32) * k_scale.astype(jnp.float32)[..., None]
        vph = vph.astype(jnp.float32) * v_scale.astype(jnp.float32)[..., None]
    qg = q.reshape(b, t, hk, rep, dh).astype(jnp.float32) * scale
    logits = jnp.einsum("btkrd,bkpd->bkrtp", qg, kph.astype(jnp.float32))
    vmask = valid[:, :, None, None, :]
    logits = jnp.where(vmask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None]) * vmask
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkrtp,bkpd->bkrtd", p, vph.astype(jnp.float32))
    # [B, Hk, rep, T, ...] -> [B, H, T, ...]
    m = m.reshape(b, h, t)
    l = l.reshape(b, h, t)
    acc = acc.reshape(b, h, t, dh)
    return m, l, acc


def dense_attn_part_quant(q, k_q, v_q, k_scale, v_scale, kv_valid, *,
                          scale=None):
    """Int8-cache context attention for tiny T without materialising a
    dequantized cache: per-(token, head) scales fold into the logits
    (k side) and into the probabilities (v side), so the MXU consumes the
    int8 tensors directly.

    q: [B, T, H, Dh]; k_q/v_q: [B, S, Hk, Dh] int8;
    k_scale/v_scale: [B, S, Hk]; kv_valid: [B, S] bool.
    """
    b, t, h, dh = q.shape
    s, hk = k_q.shape[1], k_q.shape[2]
    n_rep = h // hk
    scale = scale or (1.0 / math.sqrt(dh))
    qf = (q.astype(jnp.float32) * scale)
    kr = repeat_kv(k_q, n_rep)
    logits_q = jnp.einsum("bthd,bshd->bhts", qf, kr.astype(jnp.float32))
    ks = repeat_kv(k_scale[..., None], n_rep)[..., 0]      # [B, S, H]
    logits = logits_q * ks.transpose(0, 2, 1)[:, :, None, :]
    mask = kv_valid[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None]) * mask
    l = jnp.sum(p, axis=-1)
    vs = repeat_kv(v_scale[..., None], n_rep)[..., 0]
    p_scaled = p * vs.transpose(0, 2, 1)[:, :, None, :]
    vr = repeat_kv(v_q, n_rep)
    acc = jnp.einsum("bhts,bshd->bhtd", p_scaled, vr.astype(jnp.float32))
    return m, l, acc


def merge_attn_partials(parts):
    """Merge softmax partials from independent segments into one
    (m, l, acc) partial (un-normalised — feed the result to
    ``combine_attn_parts`` alongside other segments).  Used by the
    zero-copy partial path to fuse the kernel-routed pool segment with
    the dense tail-buffer segment before the fused step's row-select."""
    m = parts[0][0]
    for p in parts[1:]:
        m = jnp.maximum(m, p[0])
    l = 0.0
    acc = 0.0
    for (mi, li, acci) in parts:
        corr = jnp.exp(mi - m)
        l = l + li * corr
        acc = acc + acci * corr[..., None]
    return m, l, acc


def combine_attn_parts(parts, out_dtype):
    """Merge softmax partials from independent segments. -> [B, T, H, Dh]"""
    m = parts[0][0]
    for p in parts[1:]:
        m = jnp.maximum(m, p[0])
    l = 0.0
    acc = 0.0
    for (mi, li, acci) in parts:
        corr = jnp.exp(mi - m)
        l = l + li * corr
        acc = acc + acci * corr[..., None]
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(out_dtype)


# ---------------------------------------------------------------------------
# single-layer KV-cache views (draft cache) — the paged counterpart of the
# trunk read/write path in models/dense.py, over the second, smaller pool
# (k/v: [NumPagesD, block, Hk, Dh] + per-slot tables).
# ---------------------------------------------------------------------------

def layer_ctx_view(cache: dict):
    """Logical contiguous (k, v, S) view of a single-layer KV-cache dict.

    Contiguous caches return their arrays as-is; paged caches gather the
    slot's pages through the table (entries mapping to the null page read
    stale values — callers mask by ``cache["length"]``, exactly as they
    mask unwritten contiguous slots)."""
    if "page_table" in cache:
        from repro.kvcache.cache import gather_page_view
        pt = cache["page_table"]
        k = gather_page_view(cache["k"], pt)
        v = gather_page_view(cache["v"], pt)
        return k, v, k.shape[1]
    return cache["k"], cache["v"], cache["k"].shape[1]


def layer_cache_append(cache: dict, k_new, v_new, valid) -> dict:
    """Write `k_new`/`v_new` [B, T, Hk, Dh] at per-row offsets
    ``cache["length"]`` into a single-layer KV-cache dict; `valid`
    [B, T] zeroes masked entries in place (they land beyond the advanced
    length and are overwritten later, mirroring the contiguous path).
    Length bookkeeping stays with the caller."""
    zk = jnp.where(valid[:, :, None, None], k_new, 0)
    zv = jnp.where(valid[:, :, None, None], v_new, 0)
    out = dict(cache)
    if "page_table" in cache:
        from repro.kvcache.cache import paged_write_tokens
        pt = cache["page_table"]
        out["k"] = paged_write_tokens(cache["k"], pt, cache["length"], zk)
        out["v"] = paged_write_tokens(cache["v"], pt, cache["length"], zv)
        return out

    def wr(buf, new, off):
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                            (off, 0, 0))
    out["k"] = jax.vmap(wr)(cache["k"], zk, cache["length"])
    out["v"] = jax.vmap(wr)(cache["v"], zv, cache["length"])
    return out
