"""Median client-side TTFT over the window's requests. Not an end-to-end
metric: with ~108 requests it swings 4-7% between identical runs (PERF.md,
PR 24), more than a bound of at most 10% can carry."""
SOURCE = "client"


def compute(src):
    from loadgen import percentile

    ttft = src.client.get("ttft_s")
    return 1000.0 * percentile(ttft, 50) if ttft else None
