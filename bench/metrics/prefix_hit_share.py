"""Share of the prompt tokens admitted in the window that the prefix
cache served (``prefill_tokens_skipped`` against those prefilled)."""
from readers import share


def read(rec):
    skipped = rec.prefix.get("prefill_tokens_skipped", 0)
    ran = rec.stats.get("prefill_tokens", 0.0)
    return share(skipped, skipped + ran)
