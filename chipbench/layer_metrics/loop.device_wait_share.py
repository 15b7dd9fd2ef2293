"""Share of the serving thread's window suspended until a step's result is on
the host: the slack the host has. Near 0 the host sets the pace."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "device_wait")
