"""Operations and bytes the work needs, from shapes alone.

These count what the mathematics requires — a matmul of [m, k] x [k, n]
is 2mkn operations; in a layer of multi-head attention ``t`` queries
over ``s`` keys take ``4 t s H Dh`` (scores and the weighted sum of
values) — and never what
a particular kernel happens to do, so that a kernel doing wasted work
shows as a lower share of its roofline.  The counts that depend on a
model's layers come from its architecture module (``bench/archs/``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

from model import arch_of

BENCH = Path(__file__).resolve().parent


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def least_time(flops: float, nbytes: float, pk: Dict[str, float]
               ) -> Tuple[float, str]:
    """The roofline's least time and the bound that sets it."""
    tc, tm = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def attn_flops(dm, t_q: int, s_kv: int) -> float:
    """``t_q`` queries each attending ``s_kv`` keys, summed over the
    layers that attend (the architecture's count)."""
    return arch_of(dm).attn_flops(dm, t_q, s_kv)


def attn_bytes(dm, t_q: int, s_kv: int) -> float:
    """K and V of the attended keys, the queries read and the outputs
    written, summed over the layers that attend."""
    return arch_of(dm).attn_bytes(dm, t_q, s_kv)


def matmul_flops_per_token(dm, lm_head: bool = True) -> float:
    """Matrix products of every layer (+ the LM head), per token."""
    return arch_of(dm).matmul_flops_per_token(dm, lm_head)


def verify_flops(dm, t_q: int, attended: int) -> float:
    """Target-model operations of one verify row: ``t_q`` positions
    through every layer and the LM head, each attending ``attended``
    keys of context (the tree's own keys are not counted)."""
    return t_q * matmul_flops_per_token(dm) + attn_flops(dm, t_q, attended)
