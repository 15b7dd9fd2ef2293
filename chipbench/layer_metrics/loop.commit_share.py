"""Share of the serving thread's window committing and delivering tokens
(``commit_computed``, progress callbacks, ``_deliver``)."""
SOURCE = "flight"


def compute(src):
    from layer_metrics.loop_share import share

    return share(src.flight, "commit")
