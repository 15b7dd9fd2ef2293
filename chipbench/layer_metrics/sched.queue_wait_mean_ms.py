"""Mean wait for admission of the sequences admitted in the window, over
tenants and classes: the scheduler's own stamps (``scheduler.py``
``note_queue_wait``), seconds total over count."""
SOURCE = "worker_metrics"


def compute(src):
    waited = src.delta_sum("worker", "dynamo_tenant_queue_wait_seconds_total")
    count = src.delta_sum("worker", "dynamo_tenant_queue_wait_count")
    return 1000.0 * waited / count if waited is not None and count else None
