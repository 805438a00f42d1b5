"""Share of the prefill programs' device time in the traced window that
the paged prefill attention kernel (``specpv_attn_prefill``) takes."""
from named import ATTN_PREFILL, named_kernel_s
from readers import PREFILL_MODULES, modules, share


def read(rec):
    attn = named_kernel_s(rec, PREFILL_MODULES, ATTN_PREFILL)
    _, secs = modules(rec, PREFILL_MODULES)
    return share(attn, secs) if attn > 0 else None
