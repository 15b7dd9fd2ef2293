"""Median wall time of a step that carries a prefill chunk. HOST clock."""
SOURCE = "flight"


def compute(src):
    from sources import median

    return median([s["wall_ms"] for s in src.flight
                   if s.get("prefill_chunks")])
