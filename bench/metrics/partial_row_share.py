"""Share of the window's stepped decode rows that verified in PARTIAL
mode (scheduler counters ``mode_rows_*``)."""
from readers import share


def read(rec):
    rows = {m: rec.stats.get(f"mode_rows_{m}", 0.0)
            for m in ("full", "refresh", "partial")}
    return share(rows["partial"], sum(rows.values()))
