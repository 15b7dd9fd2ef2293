"""Share of the grouped matmuls' row tiles that found their expert's weights
resident: ``1 − Σ moe_experts_touched / Σ moe_tiles`` over the window's
steps (flight fields, summed over a step's expert layers). An expert's
matrix crosses HBM once a launch, so only the first of its tiles pays for
it: 0 where every touched expert fills one tile (decode steps), about two
thirds where 36 experts share 105 tiles. It is a property of the routing,
not of the kernel. A tree whose records lack ``moe_tiles`` is read as the
fewest tiles its records allow, ``max(touched, ceil(pairs / 128))`` a step:
a lower bound, so the fallback reads a little low."""
SOURCE = "flight"

ROW_TILE = 128  # dynamo_tpu/ops/grouped_matmul.py


def compute(src):
    steps = [s for s in src.flight if s.get("moe_experts_touched")]
    touched = sum(s["moe_experts_touched"] for s in steps)
    if any("moe_tiles" in s for s in steps):
        tiles = sum(s.get("moe_tiles", 0) for s in steps)
    else:
        tiles = sum(max(s["moe_experts_touched"],
                        -(-s.get("moe_pairs", 0) // ROW_TILE))
                    for s in steps)
    return 1.0 - touched / tiles if tiles else None
