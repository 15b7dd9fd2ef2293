"""Share of the serving thread's window between "the result is on the host" (a
stamp the worker thread takes) and the loop running again, plus the loop's
own yield to the process's other coroutines."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "lag")
