"""Share of the serving thread's window making flight records and metrics: the
instrument's own cost."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "record")
