"""The frontend's own view of TTFT: mean over the window of its histogram
(sum / count deltas). Bucketed, so a mean only."""
SOURCE = "frontend_metrics"


def compute(src):
    name = "dynamo_http_time_to_first_token_seconds"
    n = src.delta_sum("frontend", name + "_count")
    total = src.delta_sum("frontend", name + "_sum")
    return 1000.0 * total / n if n else None
