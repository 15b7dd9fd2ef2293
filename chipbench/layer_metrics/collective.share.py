"""Collectives' share of device busy time on the first device."""
SOURCE = "trace"


def compute(src):
    d = src.device()
    if not d or len(src.trace["devices"]) < 2:
        return None
    return 100.0 * d["collective_s"] / d["busy_s"]
