"""What the per-layer readers of the program's own spans and named
kernels share.

The serving program times its layers into the scheduler's ``stats``
(``src/repro/utils/spans.py``): ``span_s.<name>`` seconds and
``span_n.<name>`` calls, so a run's record holds their window deltas in
``rec.stats``.  Its Pallas kernels carry stable names in the device
trace (``src/repro/kernels/names.py``): the op ``<name>.<id>`` in
``rec.trace["kernels"]``.  A program without them reads as nothing: the
helpers return 0 and the readers None.
"""
from __future__ import annotations

import re
from typing import Iterable, Optional

import flops
from readers import DECODE_MODULES, traced_ticks, verify_shape

# kernel names, as src/repro/kernels/ops.py gives them
ATTN_PARTIAL = "specpv_attn_partial"
ATTN_FULL = "specpv_attn_full"
ATTN_PREFILL = "specpv_attn_prefill"

MODE_FULL = 0                   # readers.py has the other two mode ids

_ID = re.compile(r"\.\d+$")


def span_s(rec, name: str) -> float:
    return rec.stats.get("span_s." + name, 0.0)


def span_n(rec, name: str) -> float:
    return rec.stats.get("span_n." + name, 0.0)


def wait_s(rec) -> float:
    """Seconds the program spent blocked on the device: every
    ``*.wait`` span (none nests in another)."""
    return sum(v for k, v in rec.stats.items()
               if k.startswith("span_s.") and k.endswith(".wait"))


def named_kernel_s(rec, module_names: Iterable[str], name: str) -> float:
    """Device seconds of the Pallas kernel ``name`` inside the named
    programs."""
    tr = rec.trace or {}
    return sum(secs for mod, ks in tr.get("kernels", {}).items()
               if mod in module_names
               for op, (_, secs, _) in ks.items()
               if _ID.sub("", op) == name)


def attn_roofline(rec, kernel: str, modes) -> Optional[float]:
    """Share of its roofline of the decode step's attention kernel
    ``kernel`` over the rows in ``modes``: the least time
    (``flops.least_time``) of those rows' attention in the traced ticks
    over the kernel's device seconds in the decode program."""
    secs = named_kernel_s(rec, DECODE_MODULES, kernel)
    if secs <= 0:
        return None
    dm = rec.dims
    fl = by = 0.0
    for t in traced_ticks(rec):
        for _, mode, ctx, pend in t.rows:
            if mode in modes:
                tq, kv = verify_shape(rec, mode, ctx, pend)
                fl += flops.attn_flops(dm, tq, kv)
                by += flops.attn_bytes(dm, tq, kv)
    if fl <= 0:
        return None
    least, _ = flops.least_time(fl, by, rec.peaks)
    return 100.0 * least / secs
