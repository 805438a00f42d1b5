"""Block-attention kernel (``kernels/sparse_attention.py``) share of its
roofline inside the fused decode step.

Least time = max(operations / peak, bytes / bandwidth) of the attention
each traced tick's rows need (``flops.py``): a FULL or REFRESH row's
verified positions over its whole context, a PARTIAL row's over the
blocks its refresh selected (the partial budget).  Divided by the summed
device time of the kernel's calls inside the decode program.
"""
import flops
from readers import (BLOCK_ATTN_OUT, DECODE_MODULES, kernel_seconds,
                     traced_ticks, verify_shape)


def read(rec):
    kernel_s = kernel_seconds(rec, DECODE_MODULES, BLOCK_ATTN_OUT)
    if kernel_s <= 0:
        return None
    dm = rec.dims
    fl = by = 0.0
    for t in traced_ticks(rec):
        for _, mode, ctx, pend in t.rows:
            tq, kv = verify_shape(rec, mode, ctx, pend)
            fl += flops.attn_flops(dm, tq, kv)
            by += flops.attn_bytes(dm, tq, kv)
    least, _ = flops.least_time(fl, by, rec.peaks)
    return 100.0 * least / kernel_s
