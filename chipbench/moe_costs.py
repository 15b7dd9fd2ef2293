"""What the held-experts layer's grouped matrix products must do, counted
from what a step routed: the operations and bytes a roofline share of the
kernel ``ops/grouped_matmul.py`` holds its measured time against.

A (token, expert) pair is one row through three launches: gate and up
(``hidden`` -> ``ffn``) and down (``ffn`` -> ``hidden``). A launch's bytes are
what it MUST read and never more, so that the share cannot pass 100%: each
touched expert's matrix once and each pair's input row once. Writes, the
padding rows of a tile and a second read of an expert whose rows fill more
than one tile are the kernel's own cost and are not counted.

The program names each launch in the device trace
``moe_grouped_matmul_g<group>_<m|d><tokens>_<gate|up|down>``: the cache
group (layer kind) of the layers that ran it, the step program (mixed or
decode-only, and its token bucket) and the projection; a flight record's
``moe_by_group`` says what each group routed (pairs, experts touched) in
its step. No reader holds the two against each other yet: a launch takes
0.03-0.085 s of a traced slice and the harness shows a reader the ten
costliest ops only (from 0.105 s), so the share would be absent from most
runs (PERF.md section 7).
"""


def launch_ops(pairs: int, hidden: int, ffn: int) -> int:
    """Multiply-adds x 2 of ``pairs`` rows through one projection."""
    return 2 * pairs * hidden * ffn


def launch_bytes(pairs: int, experts_touched: int, hidden: int, ffn: int,
                 projection: str, itemsize: int = 2) -> int:
    row = ffn if projection == "down" else hidden  # the input row's width
    return (experts_touched * hidden * ffn + pairs * row) * itemsize


def roofline_seconds(ops: int, nbytes: int, flops_per_s: float,
                     bytes_per_s: float) -> float:
    """The least time the work can take: the slower of computing it at the
    peak and of reading its bytes at the memory's bandwidth."""
    return max(ops / flops_per_s, nbytes / bytes_per_s)
