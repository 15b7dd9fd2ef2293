"""Share of the serving thread's window building host operands in Python and
numpy (rows, block tables, sampling arrays, block allocation)."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "build")
