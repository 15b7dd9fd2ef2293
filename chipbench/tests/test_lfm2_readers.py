"""The two readers ISSUE 45 added, on made-up sources: the share of the held
experts a decode step streams (flight records + the worker's facts) and the
share of a KV page that is padding (the worker's gauge); each reads nothing,
without raising, from a program that has no such field, fact or gauge (the
parent commit's)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from sources import Sources  # noqa: E402
from test_sched_readers import load  # noqa: E402

#: lfm2-24b-a2b-pp4's worker: 64 held experts in each of 8 expert layers
FACTS = {"experts_held": [0, 64], "layers": {"full": 2, "window": 0,
                                             "dense": 2, "experts": 8,
                                             "shortconv": 8}}
#: flight records as the worker writes them (absent-when-zero): two
#: pipelined decode steps (8 rows: 25 experts a layer; 32 rows: 56), a
#: decode-only planned step, a mixed step (never counted) and an idle wait
FLIGHT = [
    {"kind": "decode_pipe", "decode_rows": 8, "moe_pairs": 256,
     "moe_experts_touched": 8 * 25, "moe_tiles": 200},
    {"kind": "decode_pipe", "decode_rows": 32, "moe_pairs": 1024,
     "moe_experts_touched": 8 * 56, "moe_tiles": 448},
    {"kind": "ragged", "decode_rows": 16, "moe_pairs": 512,
     "moe_experts_touched": 8 * 41, "moe_tiles": 328},
    {"kind": "ragged", "decode_rows": 3, "prefill_chunks": 1,
     "moe_pairs": 65632, "moe_experts_touched": 512, "moe_tiles": 1000},
    {"kind": "empty"},
]


def src(flight=FLIGHT, facts=FACTS, metrics=""):
    return Sources(client={}, flight=flight, worker_metrics=("", metrics),
                   frontend_metrics=("", ""), log="", facts=facts)


def test_touched_share_is_the_decode_steps_experts_over_all_held():
    compute = load("moe.touched_share").compute
    assert compute(src()) == pytest.approx((25 + 56 + 41) / (3 * 64))
    assert compute(src(FLIGHT[:1])) == pytest.approx(25 / 64)
    # a share of an expert-parallel layer: 16 held, one expert layer a group
    share = {"experts_held": [16, 16], "layers": {"experts": 6}}
    assert compute(src([{"kind": "decode_pipe", "moe_experts_touched": 48}],
                       share)) == pytest.approx(0.5)


@pytest.mark.parametrize("flight,facts", [
    (FLIGHT[3:], FACTS),                       # no decode-only step
    ([], FACTS),
    (FLIGHT, {}),                              # no facts at all
    (FLIGHT, {"experts_held": None, "layers": {"experts": 0}}),  # dense
    ([{"kind": "decode_pipe", "decode_rows": 4}], FACTS),  # no such field
], ids=["mixed_only", "no_records", "no_facts", "no_experts", "no_field"])
def test_touched_share_reads_nothing_where_there_is_nothing(flight, facts):
    assert load("moe.touched_share").compute(src(flight, facts)) is None


def test_lane_pad_share_is_the_gauge_in_percent():
    compute = load("cache.lane_pad_share").compute
    text = ("# HELP dynamo_kv_lane_pad_share share of a KV page's bytes\n"
            "# TYPE dynamo_kv_lane_pad_share gauge\n"
            "dynamo_kv_lane_pad_share 0.5\n"
            "dynamo_kv_usage 0.25\n")
    assert compute(src(metrics=text)) == 50.0
    assert compute(src(metrics=text.replace(" 0.5", " 0.0"))) == 0.0
    # the parent has no such gauge
    assert compute(src(metrics="dynamo_kv_usage 0.25\n")) is None
    assert compute(src()) is None
