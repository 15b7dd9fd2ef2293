"""Sequences preempted and recomputed during the window (a count)."""
SOURCE = "worker_metrics"


def compute(src):
    return src.delta_sum("worker", "dynamo_preempt_recompute_total")
