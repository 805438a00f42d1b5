"""Share of the window's decode ticks that also ran a prefill chunk."""
from readers import decode_ticks, share


def read(rec):
    ticks = decode_ticks(rec.ticks)
    return share(sum(1 for t in ticks if t.prefill_tokens), len(ticks))
