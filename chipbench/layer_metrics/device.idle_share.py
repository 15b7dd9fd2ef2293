"""1 − device busy / traced window, on the device that idled most."""
SOURCE = "trace"


def compute(src):
    d = src.device("worst_idle_device")
    return 100.0 * d["idle_share"] if d else None
