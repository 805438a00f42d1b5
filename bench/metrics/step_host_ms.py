"""Host milliseconds per fused decode step outside the device wait: the
``engine.step`` spans prepare + dispatch + post over the steps
(``engine.step.wait`` calls) in the window."""
from named import span_n, span_s


def read(rec):
    n = span_n(rec, "engine.step.wait")
    if n <= 0:
        return None
    host = sum(span_s(rec, f"engine.step.{p}")
               for p in ("prepare", "dispatch", "post"))
    return 1e3 * host / n
