"""Whole decode step's share of the chip's peak: target-model operations
of the positions each decode tick verifies (root + tree per row, a
refresh row's pending run too; attention over each row's attended
context; draft excluded), over peak x the host-clock time of those
decode steps."""
import flops
from readers import decode_ticks, verify_shape


def read(rec):
    fl = secs = 0.0
    for t in decode_ticks(rec.ticks):
        secs += t.decode_s
        for _, mode, ctx, pend in t.rows:
            fl += flops.verify_flops(rec.dims, *verify_shape(rec, mode, ctx,
                                                             pend))
    if secs <= 0 or rec.peaks is None:
        return None
    return 100.0 * fl / (rec.peaks["bf16_flops"] * secs)
