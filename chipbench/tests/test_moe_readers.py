"""The expert layer's reader added by ISSUE 38, on recorded flight lines."""

import pytest
from test_sched_readers import Src, load

#: flight records as the worker writes them (absent-when-zero): a
#: 2,048-token mixed step of Granite's cell over ten expert layers, a
#: decode-only step of six rows, and a step of a model without experts
WITH = [
    {"kind": "ragged", "moe_pairs": 102361, "moe_experts_touched": 360,
     "moe_tiles": 1049, "moe_by_group": [[102361, 360, 1049]]},
    {"kind": "decode_pipe", "moe_pairs": 301, "moe_experts_touched": 205,
     "moe_tiles": 205, "moe_by_group": [[301, 205, 205]]},
    {"kind": "decode_pipe", "decode_rows": 3},
]
#: the same steps as the parent records them
WITHOUT = [{k: v for k, v in s.items() if k != "moe_tiles"} | (
    {"moe_by_group": [g[:2] for g in s["moe_by_group"]]}
    if "moe_by_group" in s else {}) for s in WITH]


def test_weight_reuse_share_with_and_without_the_field():
    compute = load("moe.weight_reuse_share").compute
    assert compute(Src(WITH)) == pytest.approx(1 - 565 / 1254)
    assert compute(Src(WITH[:1])) == pytest.approx(1 - 360 / 1049)
    assert compute(Src(WITH[1:])) == 0.0
    # the parent: the fewest tiles the pairs can fill, ceil(102361 / 128)
    # = 800 where the kernel launched 1049 (every expert's last tile is
    # part empty), and the touched experts where they are more: a little low
    assert compute(Src(WITHOUT)) == pytest.approx(1 - 565 / 1005)
    assert compute(Src(WITHOUT[:1])) == pytest.approx(1 - 360 / 800)
    assert compute(Src(WITHOUT[1:])) == 0.0
    assert compute(Src(WITH[2:])) is None
    assert compute(Src([])) is None
