"""Share of the held experts' weights that a decode step streams: ``Σ
moe_experts_touched / (held experts × expert layers × steps)`` over the
window's decode-only records (flight fields; ``moe_experts_touched`` is
summed over a step's expert layers, counted on the device). With R rows a
step and K of E experts a token, uniform routing gives ``1 − (1 − 1/E)^(K·R)``:
0.39 at 8 rows of 4-in-64, 0.87 at 32, so beside ``sched.decode_rows_mean``
it is the curve along which a decode step's bytes grow with its batch. The
experts and the layers come from the worker's ``engine built:`` line. A tree
whose records lack the field, or a model that holds no experts, has nothing
to read."""
SOURCE = "flight"


def compute(src):
    facts = src.facts or {}
    held = (facts.get("experts_held") or [0, 0])[1]
    layers = (facts.get("layers") or {}).get("experts", 0)
    steps = [s for s in src.flight
             if s.get("moe_experts_touched") and not s.get("prefill_chunks")]
    if not held or not layers or not steps:
        return None
    return sum(s["moe_experts_touched"] for s in steps) / (
        held * layers * len(steps))
