"""Stable names for the Pallas kernels in compiled programs and traces.

An XLA op takes the last part of its JAX name stack as its name, and so
does a Pallas kernel's ``custom-call``: called from a jitted wrapper the
kernel is named after the wrapper, under ``vmap`` after the transform
(``vmap__``), and when ``vmap`` turns a kernel with per-row
scalar-prefetch tables into a loop over the rows, ``closed_call``.
``named`` gives the kernel its own name in every program that calls it,
so a profiler trace finds it as ``<name>.<id>``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.source_info_util import reset_name_stack


def named(name: str, call, rows: bool = False):
    """``call`` (one ``pallas_call``, applied to its operands) with its
    kernel named ``name``.

    ``rows=False``: under ``vmap`` the kernel is batched as JAX batches
    it (a grid axis).  ``rows=True``: under ``vmap`` the kernel runs once
    per row in a loop — what ``vmap`` lowers a kernel with batched
    scalar-prefetch operands to anyway — with every row's call named."""

    def one(*args):
        with reset_name_stack(), jax.named_scope(name):
            return call(*args)

    if not rows:
        return one

    loop = jax.custom_batching.custom_vmap(one)

    @loop.def_vmap
    def _each_row(axis_size, in_batched, *args):
        def row(i):
            return [lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                    if b else a for a, b in zip(args, in_batched)]

        shapes = jax.eval_shape(lambda *a: one(*a), *row(0))
        init = jax.tree.map(
            lambda s: jnp.empty((axis_size,) + s.shape, s.dtype), shapes)

        def body(i, acc):
            return jax.tree.map(
                lambda y, x: lax.dynamic_update_index_in_dim(y, x, i, 0),
                acc, one(*row(i)))

        out = lax.fori_loop(0, axis_size, body, init)
        return out, jax.tree.map(lambda _: True, out)

    return loop
