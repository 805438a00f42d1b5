"""Device time of the prefill programs per 1,000 prompt tokens they ran
in the traced window."""
from readers import PREFILL_MODULES, modules, traced_ticks


def read(rec):
    _, secs = modules(rec, PREFILL_MODULES)
    toks = sum(t.prefill_tokens for t in traced_ticks(rec))
    return 1e6 * secs / toks if toks and secs else None
