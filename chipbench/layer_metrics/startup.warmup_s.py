"""Seconds the worker spent warming its token-bucket signatures (its log)."""
SOURCE = "log"


def compute(src):
    import re

    m = re.search(r"ragged warmup: (\d+) token-bucket signatures in "
                  r"([\d.]+)s", src.log)
    return float(m.group(2)) if m else None
