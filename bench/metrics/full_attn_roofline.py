"""Paged full / refresh verification attention (``specpv_attn_full``)
share of its roofline inside the fused decode step: the least time of
the FULL and REFRESH rows' attention in the traced ticks (verified
positions over the whole context, ``flops.py``) over the kernel's device
seconds in the decode program."""
from named import ATTN_FULL, MODE_FULL, attn_roofline
from readers import MODE_REFRESH


def read(rec):
    return attn_roofline(rec, ATTN_FULL, (MODE_FULL, MODE_REFRESH))
