"""The Mamba-2 kernels' share of device busy time: self time of the ops
whose name holds ``mamba2_`` over the first device's busy time. A reader is
shown the ten costliest ops only (``breakdown.device_ops``): it sums the
entries that hold the name, and has nothing to read where none is listed."""
SOURCE = "trace"
NAME = "mamba2_"


def compute(src):
    d = src.device()
    if not d or not d["busy_s"]:
        return None
    mine = [s for label, s in src.trace["breakdown"]["device_ops"]
            if NAME in label.split(" = ", 1)[0]]
    return 100.0 * sum(mine) / d["busy_s"] if mine else None
