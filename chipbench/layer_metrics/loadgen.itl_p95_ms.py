"""95th percentile of the client-side gaps. Not an end-to-end metric: in the
open-loop cell it sits on the cliff between decode steps (~21 ms) and mixed
steps (70-180 ms) and swings 7% between identical runs (PERF.md, PR 24)."""
SOURCE = "client"


def compute(src):
    from loadgen import percentile

    gaps = src.client.get("gaps_s")
    return 1000.0 * percentile(gaps, 95) if gaps else None
