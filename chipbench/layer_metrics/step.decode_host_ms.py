"""Median wall time of a step that carries no prefill chunk. HOST clock
(flight ``wall_ms``: plan + execute), said so in the name."""
SOURCE = "flight"


def compute(src):
    from sources import median

    return median([s["wall_ms"] for s in src.flight
                   if not s.get("prefill_chunks") and s.get("decode_rows")])
