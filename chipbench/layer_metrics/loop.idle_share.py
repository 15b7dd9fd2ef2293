"""Share of the serving thread's window with nothing to run: no sequence at all
(``idle``) or work that no plan could take (``blocked``, the 50 ms wait)."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "idle", "blocked")
