"""Device time per annotated step outside the attention kernel and outside
collectives (first device), from the trace."""
SOURCE = "trace"


def compute(src):
    d = src.device()
    if not d or not src.trace["steps_total"]:
        return None
    rest = d["busy_s"] - d["kernel_s"] - d["collective_s"]
    return 1000.0 * rest / src.trace["steps_total"]
