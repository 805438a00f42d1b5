"""Pallas TPU kernel: Quest-style block scoring (paper eqs. (2)-(3)).

One grid step per kv head: the head's grouped queries (flattened to
``R = rep*T`` rows) and its column of the summary table are
VMEM-resident; two MXU matmuls (q @ Kmax^T, q @ Kmin^T), elementwise max,
then the mean over group heads and participating queries.  The summary
tables ``[NB, Hk, Dh]`` are viewed as ``[NB, Hk*Dh]`` (a free reshape)
so kv head ``h`` reads the TPU-aligned ``(NB, Dh)`` tile at column
block ``h``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.names import named


def _kernel(q_ref, kmax_ref, kmin_ref, qw_ref, out_ref):
    q = q_ref[0].astype(jnp.float32)                      # [R, Dh]
    dims = (((1,), (1,)), ((), ()))
    smax = jax.lax.dot_general(q, kmax_ref[...].astype(jnp.float32), dims,
                               preferred_element_type=jnp.float32)
    smin = jax.lax.dot_general(q, kmin_ref[...].astype(jnp.float32), dims,
                               preferred_element_type=jnp.float32)
    s = jnp.maximum(smax, smin)                           # [R, NB]
    w = qw_ref[...]                                       # [R, 1]
    # w repeats each query's weight over its rep group heads, so the
    # weighted row sum over sum(w) is the mean over heads and queries
    out = (jnp.sum(s * w, axis=0, keepdims=True)
           / jnp.maximum(jnp.sum(w), 1e-9))
    out_ref[0] = out


def retrieval_score_pallas(q, kmax, kmin, q_weight, *,
                           interpret: bool = True):
    """q: [T, H, Dh]; kmax/kmin: [NB, Hk, Dh]; q_weight: [T].
    Returns scores [Hk, NB] fp32 (paper score mode, mean reduction).
    The kernel is named ``specpv_retrieval_score`` in compiled programs
    (``kernels/names.py``)."""
    t, h, dh = q.shape
    nb, hk, _ = kmax.shape
    rep = h // hk
    r = rep * t
    qg = q.reshape(t, hk, rep, dh).transpose(1, 2, 0, 3).reshape(hk, r, dh)
    qw = jnp.tile(q_weight.astype(jnp.float32), rep)[:, None]   # [R, 1]
    fn = named("specpv_retrieval_score", pl.pallas_call(
        _kernel,
        grid=(hk,),
        in_specs=[
            pl.BlockSpec((1, r, dh), lambda i: (i, 0, 0)),
            pl.BlockSpec((nb, dh), lambda i: (0, i)),
            pl.BlockSpec((nb, dh), lambda i: (0, i)),
            pl.BlockSpec((r, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hk, 1, nb), jnp.float32),
        interpret=interpret))
    out = fn(qg, kmax.reshape(nb, hk * dh), kmin.reshape(nb, hk * dh), qw)
    return out.reshape(hk, nb)
