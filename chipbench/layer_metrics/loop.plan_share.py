"""Share of the serving thread's window inside ``scheduler.plan()``."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "plan")
