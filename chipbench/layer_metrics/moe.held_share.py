"""Share of the window's routed (token, expert) assignments that went to the
experts this worker holds (Δ ``dynamo_moe_assignments_total``, counted on
the device by the steps themselves): held / routed-over at uniform routing."""
SOURCE = "worker_metrics"


def compute(src):
    d = src.delta("worker", "dynamo_moe_assignments_total")
    held = sum(v for k, v in d.items() if 'to="held"' in k)
    every = sum(v for k, v in d.items() if 'to="all"' in k)
    return held / every if every else None
