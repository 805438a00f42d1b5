"""Mean device time of one fused decode step (the engine's ``jit_run``
program) in the traced window."""
from readers import DECODE_MODULES, modules


def read(rec):
    calls, secs = modules(rec, DECODE_MODULES)
    return 1e3 * secs / calls if calls else None
