"""Plain reference of the dense GQA decoder (Qwen3 without q/k norm),
and of what SpecPV's partial verification makes it attend.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, no kernels, no cache, no batching: pre-norm RMSNorm blocks,
grouped-query attention with split-half RoPE, a SwiGLU MLP, a final
RMSNorm and the LM head (the embedding's transpose when tied).  Query
head ``j`` reads kv head ``j // (heads / kv_heads)``.  It runs layer by
layer, and inside a layer in blocks of query rows, so a 25k-token
sequence fits next to the weights on one 16 GB chip.

``forward`` is the model over a whole sequence with a causal mask.
``segment`` is the model over a run of tokens verified in PARTIAL mode
after a refresh (SpecPV, arXiv:2512.02337 §3): each attends, at each
layer and kv head, only the blocks ``select`` picks — the sink blocks,
the local window ending at the refresh's committed length, and the
retrieval budget's best blocks by the paper's eq. (2) score
``max(q.Kmax, q.Kmin)`` averaged over the head group and over the
refresh's queries — plus the run's own earlier tokens, whose keys are
those the run computed.

``fp8=True`` is the control: every linear layer and the LM head take
their inputs rounded to float8 e4m3 (activations scaled per row,
weights per output column, accumulation in float32) — the step below
the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_ROWS = 256        # query rows per attention block
MLP_ROWS = 2048     # token rows per MLP block
NEG = -1e30


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the value keeps float32 storage)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    """x [..., k] @ w [k, n] in float32 (fp8: both inputs rounded)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    """x [S, H, Dh]: rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = pos[:, None].astype(F32) * jnp.asarray(inv, F32)      # [S, Dh/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(fn, x, rows: int):
    """``fn`` over row blocks of ``x`` [S, ...] (zero-padded to whole
    blocks), results concatenated back to S rows."""
    s = x.shape[0]
    n = -(-s // rows)
    xp = jnp.pad(x, [(0, n * rows - s)] + [(0, 0)] * (x.ndim - 1))
    y = jax.lax.map(fn, xp.reshape((n, rows) + x.shape[1:]))
    return y.reshape((n * rows,) + y.shape[2:])[:s]


def _qkv(h, lw, pos, dims, fp8):
    heads, kv_heads, dh, eps, theta = dims
    s = h.shape[0]
    x = _rms(h, lw["norm1"], eps)
    a = lw["attn"]
    q = _rope(_mm(x, a["wq"], fp8).reshape(s, heads, dh), pos, theta)
    k = _rope(_mm(x, a["wk"], fp8).reshape(s, kv_heads, dh), pos, theta)
    v = _mm(x, a["wv"], fp8).reshape(s, kv_heads, dh)
    return q, k, v


def _attend(q, k, v, ok, dims):
    """q [R, H, Dh] over k/v [Hk, S, Dh] with mask ok [Hk, R, S]."""
    heads, kv_heads, dh = dims[:3]
    rep = heads // kv_heads
    qg = q.reshape(-1, kv_heads, rep, dh)
    sc = jnp.einsum("rgjd,gsd->gjrs", qg, k, precision=HI) / np.sqrt(dh)
    sc = jnp.where(ok[:, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("gjrs,gsd->rgjd", p, v, precision=HI)
    return o.reshape(-1, heads * dh)


def _finish(h, att, lw, dims, fp8):
    """Attention output projection, then the MLP block."""
    eps = dims[3]
    h = h + _mm(att, lw["attn"]["wo"], fp8)
    m = lw["mlp"]

    def mlp(xb):
        xn = _rms(xb, lw["norm2"], eps)
        return _mm(jax.nn.silu(_mm(xn, m["wg"], fp8)) * _mm(xn, m["wi"], fp8),
                   m["wo"], fp8)

    return h + _blocks(mlp, h, MLP_ROWS)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(h, lw, pos, q_idx, *, dims, fp8: bool):
    """One causal layer over the whole sequence.  Returns the new hidden
    state, this layer's keys and values [Hk, S, Dh] and the queries at
    ``q_idx``."""
    heads, kv_heads, dh = dims[:3]
    s = h.shape[0]
    q, k, v = _qkv(h, lw, pos, dims, fp8)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def attend(blk):
        qb, pb = blk
        ok = pos[None, None, :] <= pb[None, :, None]
        return _attend(qb, kt, vt, jnp.broadcast_to(
            ok, (kv_heads, pb.shape[0], s)), dims)

    n = -(-s // Q_ROWS)
    pad = n * Q_ROWS - s
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(n, Q_ROWS, heads, dh)
    pp = jnp.pad(pos, [(0, pad)]).reshape(n, Q_ROWS)
    att = jax.lax.map(attend, (qp, pp)).reshape(n * Q_ROWS, -1)[:s]
    return _finish(h, att, lw, dims, fp8), kt, vt, q[q_idx]


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _seg_layer(h, lw, pos, seg_n, k_sel, v_sel, sel_ok, *, dims, fp8: bool):
    """One layer over a PARTIAL run: h [R, d] of the run's tokens (the
    first ``seg_n`` real, the rest padding), attending the selected
    keys ``k_sel`` [Hk, P, Dh] where ``sel_ok`` [Hk, P], and the run's
    own keys up to each token."""
    kv_heads = dims[1]
    r = h.shape[0]
    q, k, v = _qkv(h, lw, pos, dims, fp8)
    kk = jnp.concatenate([k_sel, k.transpose(1, 0, 2)], axis=1)
    vv = jnp.concatenate([v_sel, v.transpose(1, 0, 2)], axis=1)
    i = jnp.arange(r)
    own = (i[None, :] <= i[:, None]) & (i[None, :] < seg_n)      # [R, R]
    ok = jnp.concatenate([jnp.broadcast_to(sel_ok[:, None], (kv_heads, r,
                                                             sel_ok.shape[1])),
                          jnp.broadcast_to(own[None], (kv_heads, r, r))], -1)
    return _finish(h, _attend(q, kk, vv, ok, dims), lw, dims, fp8)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "fp8"))
def _head(h, final_norm, w, *, eps: float, tied: bool, fp8: bool):
    x = _rms(h, final_norm, eps)
    w = w.T if tied else w
    return _mm(x, w, fp8)


def _dims(dm):
    return (dm.heads, dm.kv_heads, dm.head_dim, dm.eps, dm.theta)


def _layers(weights, i):
    return jax.tree_util.tree_map(lambda a: a[i], weights["decoder"]["slots"][0])


def head(dm, weights, h, fp8: bool = False) -> np.ndarray:
    w = weights["embed"] if dm.tied else weights["head"]
    return np.asarray(_head(h, weights["final_norm"], w, eps=dm.eps,
                            tied=dm.tied, fp8=fp8))


def forward(dm, weights, tokens: np.ndarray, q_idx: np.ndarray, *,
            fp8: bool = False):
    """The causal model over ``tokens`` [S].  Returns the final hidden
    state [S, d], every layer's keys and values [Hk, S, Dh] (float32,
    RoPE applied), and every layer's queries at ``q_idx`` [n, H, Dh]."""
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    qi = jnp.asarray(np.asarray(q_idx, np.int32))
    h = weights["embed"][tokens].astype(F32)
    ks, vs, qs = [], [], []
    for i in range(dm.layers):
        h, k, v, q = _layer(h, _layers(weights, i), pos, qi, dims=_dims(dm),
                            fp8=fp8)
        ks.append(k)
        vs.append(v)
        qs.append(q)
    return h, ks, vs, qs


@functools.partial(jax.jit, static_argnames=("bs",))
def _block_scores(k, q, qw, length, *, bs: int):
    """Eq. (2) scores [Hk, NB] of every block of ``k`` [Hk, S, Dh] (keys
    at positions >= ``length`` excluded from the block summaries), as
    the mean over the queries ``q`` [T, H, Dh] weighted by ``qw`` [T]
    and over each kv head's query heads."""
    kv_heads, s, dh = k.shape
    kb = k.reshape(kv_heads, s // bs, bs, dh)
    valid = (jnp.arange(s) < length).reshape(1, s // bs, bs, 1)
    kmax = jnp.max(jnp.where(valid, kb, -jnp.inf), axis=2)      # [Hk, NB, Dh]
    kmin = jnp.min(jnp.where(valid, kb, jnp.inf), axis=2)
    qg = q.reshape(q.shape[0], kv_heads, -1, dh)                 # [T, Hk, r, Dh]
    sc = jnp.maximum(jnp.einsum("tgrd,gnd->tgrn", qg, kmax, precision=HI),
                     jnp.einsum("tgrd,gnd->tgrn", qg, kmin, precision=HI))
    sc = jnp.where(jnp.isfinite(sc), sc, 0.0).mean(axis=2)       # [T, Hk, NB]
    return jnp.einsum("t,tgn->gn", qw, sc, precision=HI) / jnp.sum(qw)


def select(spec: dict, k, q, length: int) -> np.ndarray:
    """Blocks a refresh selects at one layer: [Hk, NS] logical block ids
    (-1 = unused retrieval rank).  ``k`` [Hk, S, Dh] holds the committed
    keys (the first ``length`` count; S a whole number of blocks), ``q``
    [T, H, Dh] the refresh's queries, each weighing 1 in the mean (paper
    eqs. (1)-(3))."""
    if (spec.get("score_mode", "paper"), spec.get("reduction", "mean")
            ) != ("paper", "mean"):
        raise ValueError("the reference scores blocks by eq. (2), mean")
    bs = spec["block_size"]
    n_sink, n_ret = spec["num_sink_blocks"], spec["retrieval_budget_blocks"]
    n_loc = spec["local_window_blocks"]
    t = q.shape[0]
    tp = -(-t // 128) * 128
    qp = jnp.pad(q, ((0, tp - t), (0, 0), (0, 0)))
    qw = jnp.asarray(np.arange(tp) < t, F32)
    s = np.asarray(_block_scores(k, qp, qw, length, bs=bs))     # [Hk, NB]
    nb = -(-length // bs)
    loc_lo = max(nb - n_loc, 0)
    cand = np.arange(n_sink, loc_lo)
    out = np.full((k.shape[0], n_sink + n_ret + n_loc), -1, np.int64)
    out[:, :n_sink] = np.arange(n_sink)
    out[:, n_sink + n_ret:] = loc_lo + np.arange(n_loc)
    if cand.size:
        # top scores, ties to the lower block id
        order = np.argsort(-s[:, cand], axis=1, kind="stable")[:, :n_ret]
        out[:, n_sink: n_sink + order.shape[1]] = cand[order]
    return out


def select_layers(dm, spec: dict, ks, qs, length: int) -> list:
    """Blocks a refresh selects at every layer (``select`` on each):
    ``ks[l]`` the layer's committed keys, ``qs[l]`` the refresh's
    queries there."""
    return [select(spec, k, q, length) for k, q in zip(ks, qs)]


def segment(dm, weights, ks, vs, spec: dict, sel, length: int,
            tokens: np.ndarray, start: int, *, fp8: bool = False,
            rows: int = 128):
    """Final hidden state [n, d] of the PARTIAL run ``tokens`` [n] at
    positions ``start ..``: at layer ``l`` the run attends the blocks
    ``sel[l]`` [Hk, NS] of the committed keys (positions < ``length``)
    and its own earlier tokens."""
    bs = spec["block_size"]
    n = len(tokens)
    r = -(-n // rows) * rows
    toks = np.zeros((r,), np.int32)
    toks[:n] = tokens
    pos = jnp.asarray(start + np.arange(r), jnp.int32)
    h = weights["embed"][jnp.asarray(toks)].astype(F32)
    for i in range(dm.layers):
        blk = np.asarray(sel[i])                                 # [Hk, NS]
        p = (np.maximum(blk, 0)[:, :, None] * bs
             + np.arange(bs)[None, None]).reshape(blk.shape[0], -1)
        ok = ((blk >= 0)[:, :, None].repeat(bs, 2).reshape(p.shape)
              & (p < length))
        p = np.minimum(p, length - 1)
        hk = np.arange(blk.shape[0])[:, None]
        k_sel = ks[i][hk, jnp.asarray(p)]
        v_sel = vs[i][hk, jnp.asarray(p)]
        h = _seg_layer(h, _layers(weights, i), pos, n, k_sel, v_sel,
                       jnp.asarray(ok), dims=_dims(dm), fp8=fp8)
    return h[:n]
