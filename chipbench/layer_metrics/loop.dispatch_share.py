"""Share of the serving thread's window handing work to the runtime: the
operands onto the device and the eager ops that edit them (``put``), the
jitted step's call (``dispatch``), the pipelined step's sampler call and
starting the copy task (``sample``)."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "put", "dispatch", "sample")
