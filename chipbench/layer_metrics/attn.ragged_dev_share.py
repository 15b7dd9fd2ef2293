"""The ragged attention kernel's share of device busy time, every layer
kind together (the kernel keeps one name)."""
SOURCE = "trace"


def compute(src):
    d = src.device()
    if not d or not d["busy_s"] or not src.trace["kernel_on_device"]:
        return None
    return 100.0 * d["kernel_s"] / d["busy_s"]
