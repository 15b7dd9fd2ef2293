"""The expert layer's reader added by ISSUE 46, on recorded flight lines."""

import pytest
from test_sched_readers import Src, load

#: flight records as the worker writes them (absent-when-zero): a
#: 2,048-token mixed step of Granite's cell over ten expert layers (half of
#: the pairs held here), two decode-only steps in the 8-token program (six
#: and three rows of eight: the padding's pairs are fetched by nobody), a
#: step of a model without experts
WITH = [
    {"kind": "ragged", "moe_pairs": 102361, "moe_experts_touched": 360,
     "moe_tiles": 1049, "moe_by_group": [[102361, 360, 1049]],
     "moe_combine_rows": 102361, "moe_combine_rows_max": 204800},
    {"kind": "decode_pipe", "moe_pairs": 301, "moe_experts_touched": 205,
     "moe_tiles": 205, "moe_by_group": [[301, 205, 205]],
     "moe_combine_rows": 301, "moe_combine_rows_max": 800},
    {"kind": "decode_pipe", "moe_pairs": 148, "moe_experts_touched": 120,
     "moe_tiles": 120, "moe_by_group": [[148, 120, 120]],
     "moe_combine_rows": 148, "moe_combine_rows_max": 800},
    {"kind": "decode_pipe", "decode_rows": 3},
]
#: the same steps as the parent records them
WITHOUT = [{k: v for k, v in s.items() if not k.startswith("moe_combine")}
           for s in WITH]


def test_combine_read_share_with_and_without_the_fields():
    compute = load("moe.combine_read_share").compute
    assert compute(Src(WITH)) == pytest.approx(102810 / 206400)
    assert compute(Src(WITH[:1])) == pytest.approx(102361 / 204800)
    assert compute(Src(WITH[1:])) == pytest.approx(449 / 1600)
    # a step that held no pair still counts its worst case
    none_here = dict(WITH[2], moe_combine_rows=0)
    assert compute(Src([none_here])) == 0.0
    assert compute(Src(WITHOUT)) is None   # the parent: nothing to read
    assert compute(Src(WITH[3:])) is None  # no expert layer
    assert compute(Src([])) is None
