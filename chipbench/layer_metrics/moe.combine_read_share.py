"""Share of the held-experts layer's worst case that its read-back fetched:
``Σ moe_combine_rows / Σ moe_combine_rows_max`` over the window's steps
(flight fields, summed over a step's expert layers). Both are counted on the
device by the layer itself (``engine/model.py:_mlp_moe_held``, two entries
of its counter vector): the rows of the dropless buffer that the read-back
fetched — the copies ``ops/moe_combine.py`` started, one a (token, choice)
pair whose expert is held here — beside the rows a read-back of every pair
would fetch, the step program's PADDED token count × K a layer (what the
gather this kernel replaced moved). About the share of the routed experts
that are held, times the share of a step's tokens that are no padding.
``None`` on a tree whose records lack the fields (and for a model without
experts)."""
SOURCE = "flight"


def compute(src):
    steps = [s for s in src.flight if s.get("moe_combine_rows_max")]
    worst = sum(s["moe_combine_rows_max"] for s in steps)
    if not worst:
        return None
    return sum(s.get("moe_combine_rows", 0) for s in steps) / worst
