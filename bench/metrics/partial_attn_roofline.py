"""Zero-copy partial verification attention (``specpv_attn_partial``)
share of its roofline inside the fused decode step: the least time of
the PARTIAL rows' attention in the traced ticks (verified positions over
the partial budget, ``flops.py``) over the kernel's device seconds in
the decode program.  In a tick that mixes modes every row runs the
kernel; only PARTIAL rows count as work."""
from named import ATTN_PARTIAL, attn_roofline
from readers import MODE_PARTIAL


def read(rec):
    return attn_roofline(rec, ATTN_PARTIAL, (MODE_PARTIAL,))
