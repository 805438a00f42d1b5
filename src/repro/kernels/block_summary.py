"""Pallas TPU kernel: per-block key summaries (paper eq. (1)).

Grid walks the cache blocks; each step reduces one [block, Hk, Dh] KV tile
(VMEM-resident) to elementwise max/min.  The cache length arrives via
scalar prefetch so partially-filled tail blocks mask correctly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.names import named


def _kernel(len_ref, k_ref, kmax_ref, kmin_ref, *, block_size: int):
    i = pl.program_id(0)
    tok = (i * block_size
           + jax.lax.broadcasted_iota(jnp.int32, (block_size, 1, 1), 0))
    valid = tok < len_ref[0]
    kf = k_ref[...].astype(jnp.float32)
    any_valid = jnp.any(valid)
    kmax = jnp.max(jnp.where(valid, kf, -1e30), axis=0, keepdims=True)
    kmin = jnp.min(jnp.where(valid, kf, 1e30), axis=0, keepdims=True)
    kmax_ref[...] = jnp.where(any_valid, kmax, 0.0)
    kmin_ref[...] = jnp.where(any_valid, kmin, 0.0)


def block_summary_pallas(k, length, block_size: int, *,
                         interpret: bool = True):
    """k: [S, Hk, Dh]; length: scalar int32.  Returns (kmax, kmin):
    [NB, Hk, Dh] fp32.  The kernel is named ``specpv_block_summary`` in
    compiled programs (``kernels/names.py``); under ``vmap`` it runs once
    per row."""
    s, hk, dh = k.shape
    nb = s // block_size
    k = k[: nb * block_size]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_size, hk, dh),
                               lambda i, len_ref: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, hk, dh), lambda i, len_ref: (i, 0, 0)),
                   pl.BlockSpec((1, hk, dh), lambda i, len_ref: (i, 0, 0))],
    )
    out_shape = [jax.ShapeDtypeStruct((nb, hk, dh), jnp.float32),
                 jax.ShapeDtypeStruct((nb, hk, dh), jnp.float32)]
    fn = named("specpv_block_summary", pl.pallas_call(
        functools.partial(_kernel, block_size=block_size),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret),
        rows=True)
    length = jnp.asarray(length, jnp.int32).reshape(1)
    return tuple(fn(length, k))
