"""Requests waiting for admission, mean over the window's steps."""
SOURCE = "flight"


def compute(src):
    from sources import mean

    return mean([s.get("waiting", 0) for s in src.flight])
