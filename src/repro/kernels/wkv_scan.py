"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence (beyond-paper kernel).

The Finch recurrence  s_t = diag(w_t) s_{t-1} + k_t v_t^T,
                      y_t = r_t (s_{t-1} + diag(u) k_t v_t^T)
is sequential over time but each step is a dk x dk rank-1 update — ideal
for keeping the state resident in VMEM while streaming (r, k, v, w) time
chunks HBM->VMEM.  Grid: (H, T/chunk); the per-head state never leaves
VMEM between chunks (contrast the pure-JAX lax.scan, which round-trips the
state through HBM every step).

Validated in interpret mode against the pure-jnp oracle (ref_wkv below /
tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def wkv_ref(r, k, v, w, u, s0):
    """Oracle.  r/k/v/w: [T, H, dk] fp32; u: [H, dk]; s0: [H, dk, dk].
    Returns (y [T, H, dk], s_final [H, dk, dk])."""
    def step(s, x):
        rt, kt, vt, wt = x
        kv = kt[:, :, None] * vt[:, None, :]              # [H, dk, dk]
        yt = jnp.einsum("hi,hij->hj", rt, s + u[:, :, None] * kv)
        s = wt[:, :, None] * s + kv
        return s, yt
    s, ys = jax.lax.scan(step, s0, (r, k, v, w))
    return ys, s


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, *, chunk: int, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[:, 0].astype(jnp.float32)                   # [chunk, dk]
    k = k_ref[:, 0].astype(jnp.float32)
    v = v_ref[:, 0].astype(jnp.float32)
    w = w_ref[:, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                      # [dk]

    def step(i, s):
        kv = k[i][:, None] * v[i][None, :]                # [dk, dk]
        y = (r[i][None] @ (s + u[:, None] * kv))[0]       # [dk]
        y_ref[pl.ds(i, 1)] = y[None, None].astype(y_ref.dtype)
        return w[i][:, None] * s + kv

    s = jax.lax.fori_loop(0, chunk, step, s_scr[...])
    s_scr[...] = s

    @pl.when(c == n_chunks - 1)
    def _emit():
        sout_ref[0] = s_scr[...]


def wkv_pallas(r, k, v, w, u, s0, *, chunk: int = 64,
               interpret: bool = True):
    """r/k/v/w: [T, H, dk]; u: [H, dk]; s0: [H, dk, dk].
    Returns (y [T, H, dk] fp32, s_final [H, dk, dk] fp32)."""
    t, h, dk = r.shape
    assert t % chunk == 0, "pad T to a chunk multiple"
    n_chunks = t // chunk
    grid = (h, n_chunks)

    def tspec():
        return pl.BlockSpec((chunk, 1, dk), lambda hh, cc: (cc, hh, 0))

    fn = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[tspec(), tspec(), tspec(), tspec(),
                  pl.BlockSpec((1, dk), lambda hh, cc: (hh, 0)),
                  pl.BlockSpec((1, dk, dk), lambda hh, cc: (hh, 0, 0))],
        out_specs=[tspec(),
                   pl.BlockSpec((1, dk, dk), lambda hh, cc: (hh, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, h, dk), jnp.float32),
                   jax.ShapeDtypeStruct((h, dk, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret)
    return tuple(fn(r, k, v, w, u, s0))
