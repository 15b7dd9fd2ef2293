"""The busiest held expert's tokens over the mean held expert's, in the
window (Δ ``dynamo_moe_expert_tokens_total`` by expert, summed over the
expert layers): 1.0 is an even load; a straggler's rows fill more tiles."""
SOURCE = "worker_metrics"


def compute(src):
    load = list(src.delta("worker", "dynamo_moe_expert_tokens_total").values())
    if not load or not sum(load):
        return None
    return max(load) / (sum(load) / len(load))
