"""Decode rows per step, mean over the steps that carry any."""
SOURCE = "flight"


def compute(src):
    from sources import mean

    return mean([s["decode_rows"] for s in src.flight
                 if s.get("decode_rows")])
