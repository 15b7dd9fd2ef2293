"""The scheduler's reader added by ISSUE 34, on hand-made flight records."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Src:
    def __init__(self, flight):
        self.flight = list(flight)


STEPS = [
    # a long prompt's chunk beside 3 decode rows; 2 admitted prompts got none
    {"kind": "ragged", "running": 6, "decode_rows": 3, "prefill_chunks": 1},
    # a decode-only step: 5 rows ran, 1 ready row did not fit
    {"kind": "ragged", "running": 6, "decode_rows": 5, "prefill_chunks": 0,
     "starved_decode": 1},
    # every waiting prompt got its chunk
    {"kind": "ragged", "running": 4, "decode_rows": 1, "prefill_chunks": 3},
    # not a planned step: never read
    {"kind": "decode_pipe", "running": 9, "decode_rows": 2},
]


def test_prefill_blocked_mean_with_and_without_the_field():
    compute = load("sched.prefill_blocked_mean").compute
    # the parent's records: what running leaves over, 2 + 0 + 0 over 3
    assert compute(Src(STEPS)) == pytest.approx(2 / 3)
    # records that carry the field are read by it alone
    mine = [dict(s, prefill_blocked=b) for s, b in zip(STEPS, (1, 0, 0, 7))]
    assert compute(Src(mine)) == pytest.approx(1 / 3)
    # more rows than running (a row ended in the step) never reads below 0
    assert compute(Src([dict(STEPS[2], running=2)])) == 0.0
    assert compute(Src([])) is None
    assert compute(Src([STEPS[3]])) is None
