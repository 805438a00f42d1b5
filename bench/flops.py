"""Operations and bytes the work needs, from shapes alone.

These count what the mathematics requires — a matmul of [m, k] x [k, n]
is 2mkn operations; attention of ``t`` queries over ``s`` keys is
``4 t s H Dh`` (scores and the weighted sum of values) — and never what
a particular kernel happens to do, so that a kernel doing wasted work
shows as a lower share of its roofline.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

BENCH = Path(__file__).resolve().parent
BF16 = 2


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
    """``t_q`` queries each attending ``s_kv`` keys, all heads."""
    return 4.0 * t_q * s_kv * dm.heads * dm.head_dim


def attn_bytes(dm, t_q: int, s_kv: int, itemsize: int = BF16) -> float:
    """K and V of the attended keys (read once per kv head) plus the
    queries read and the outputs written."""
    kv = 2.0 * s_kv * dm.kv_heads * dm.head_dim * itemsize
    qo = 2.0 * t_q * dm.heads * dm.head_dim * itemsize
    return kv + qo


def matmul_flops_per_token(dm, lm_head: bool = True) -> float:
    """Projections and MLP of every layer (+ the LM head), per token."""
    d = dm.d
    per_layer = 2.0 * (d * dm.q_width + 2 * d * dm.kv_width
                       + dm.q_width * d + 3 * d * dm.ff)
    return dm.layers * per_layer + (2.0 * d * dm.vocab if lm_head else 0.0)


def verify_flops(dm, t_q: int, attended: int) -> float:
    """Target-model operations of one verify row: ``t_q`` positions
    through every layer and the LM head, each attending ``attended``
    keys of context (the tree's own keys are not counted)."""
    return (t_q * matmul_flops_per_token(dm)
            + dm.layers * attn_flops(dm, t_q, attended))
