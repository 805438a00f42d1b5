"""Prefill programs' share of the chip's peak: the target model's matrix
products for the prompt tokens the traced ticks prefilled
(``flops.matmul_flops_per_token(dm, lm_head=False)`` each), over peak x
the device seconds of the prefill programs (``PREFILL_MODULES``) in the
traced window.

Attention is left out of the operations: a tick records how many prompt
tokens it ran, not at which positions of their prompts, so the keys
each chunk attended are not known here.  The share therefore reads low
by attention's part of prefill, more so at long contexts."""
import flops
from readers import PREFILL_MODULES, modules, traced_ticks


def read(rec):
    _, secs = modules(rec, PREFILL_MODULES)
    toks = sum(t.prefill_tokens for t in traced_ticks(rec))
    if not toks or secs <= 0 or rec.peaks is None:
        return None
    fl = toks * flops.matmul_flops_per_token(rec.dims, lm_head=False)
    return 100.0 * fl / (rec.peaks["bf16_flops"] * secs)
