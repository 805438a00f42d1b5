"""Shared arithmetic of the per-layer metric readers (``metrics/``).

A reader gets the run's record: the window's ticks (``serve.Tick``), the
scheduler's and prefix cache's counters over the window, the compile
events inside it and, in a traced run, the reduced trace
(``trace.reduce``).  Program and kernel names as the trace shows them
are kept here, in one place.
"""
from __future__ import annotations

import re
from typing import List, Optional

# XLA program names of the engine's jitted steps, as the trace's
# "XLA Modules" line shows them (numeric ids stripped)
DECODE_MODULES = ("jit_run",)
PREFILL_MODULES = ("jit__prefill_chunk_fused", "jit__prefill_chunk")
# The block-attention Pallas kernel (kernels/sparse_attention.py) carries
# no name of its own into the trace; it is the Pallas custom call whose
# result is its flash partials (m [H, T, 1], l [H, T, 1], acc [H, T, Dh],
# float32).  The decode step's only other kernel, the refresh's retrieval
# scores, returns one score array.
BLOCK_ATTN_OUT = re.compile(r"^\(f32\[[0-9,]+,1\]\S*, f32\[[0-9,]+,1\]\S*, "
                            r"f32\[[0-9,]+\]\S*\)$")

MODE_REFRESH, MODE_PARTIAL = 1, 2


def verify_shape(rec, mode: int, ctx: int, pend: int):
    """(queries, attended context) of one decode row: the root (a
    REFRESH row: its whole pending run) and every tree node, over the
    row's whole context (a PARTIAL row: the partial budget)."""
    tq = (pend if mode == MODE_REFRESH else 1) + rec.tree_nodes
    kv = rec.spec.partial_budget_tokens if mode == MODE_PARTIAL else ctx
    return tq, kv


def modules(rec, names) -> List[float]:
    """[calls, device seconds] summed over the named programs."""
    out = [0, 0.0]
    for n, (c, s) in rec.trace["modules"].items():
        if n in names:
            out[0] += c
            out[1] += s
    return out


def kernel_seconds(rec, module_names, out_type) -> float:
    """Device seconds of the Pallas kernels whose result type matches
    ``out_type``, inside the named programs."""
    return sum(secs for mod, ks in rec.trace["kernels"].items()
               if mod in module_names
               for _, secs, out in ks.values() if out_type.match(out))


def traced_ticks(rec):
    """The ticks that ran inside the traced part of the window."""
    lo, hi = rec.trace_host
    return [t for t in rec.ticks if t.t0 >= lo and t.t1 <= hi]


def decode_ticks(ticks):
    return [t for t in ticks if t.rows]


def share(num: float, den: float) -> Optional[float]:
    return None if den <= 0 else 100.0 * num / den
