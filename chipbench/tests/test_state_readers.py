"""The readers and the cost functions ISSUE 36 added for a model with
recurrent state, on hand-made flight records and a recorded ``breakdown``;
and that each reads nothing, without raising, from a program that has no
such field, fact or op (the parent commit's)."""

import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import ssm_costs  # noqa: E402
from sources import Sources  # noqa: E402

H, P, N = 128, 64, 128


def load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FACTS = {"state_slots": 64, "state_layers": 9,
         "mamba": {"heads": H, "d_head": P, "d_state": N}}
#: what a traced run's top ten held (my chip run's shape): two launch sites
#: of the decode-only program at 16 tokens, one of the mixed 2,048 program;
#: the fourth site (l5x4 of m2048) fell outside the ten
OPS = [
    ["%fusion.812 = bf16[16,16768]{1,0} fusion(...)", 0.90],
    ["%mamba2_decode_update_l0x5_d16.3 = (f32[9,65,64,128,128], f32[16,64,128"
     "]) custom-call(...)", 0.200],
    ["%mamba2_decode_update_l5x4_d16.7 = (f32[9,65,64,128,128]) custom-call("
     "...)", 0.160],
    ["%mamba2_decode_update_l0x5_m2048.9 = (f32[9,65,64,128,128]) custom-call"
     "(...)", 0.002],
    ["%ragged_paged_attention.15 = bf16[16,32,128] custom-call(...)", 0.03],
]


def src(flight, ops=OPS, facts=FACTS):
    trace = {"kind": "TPU v5 lite", "first_device": "/device:TPU:0",
             "devices": {"/device:TPU:0": {"busy_s": 3.0}},
             "asked": {"on_epoch": 100.0, "stop_epoch": 104.0},
             "breakdown": {"device_ops": ops}}
    return Sources(client={}, flight=flight, worker_metrics=("", ""),
                   frontend_metrics=("", ""), log="", facts=facts,
                   trace=trace)


FLIGHT = (
    # before the slice: never counted
    [{"t": 99.0, "state_program": "d16", "state_rows_decode": 12,
      "state_slots_used": 12}]
    + [{"t": 100.0 + 0.01 * i, "state_program": "d16",
        "state_rows_decode": 10, "state_slots_used": 16}
       for i in range(300)]
    + [{"t": 103.5, "state_program": "m2048", "state_rows_decode": 10,
        "state_rows_prefill": 1, "state_slots_used": 32}])


def test_slots_used_share_is_the_mean_over_the_window():
    compute = load("state.slots_used_share").compute
    want = 100.0 * (12 + 300 * 16 + 32) / 302 / 64
    assert compute(src(FLIGHT)) == pytest.approx(want)
    # the parent's worker states no slots and its records carry no field
    assert compute(src(FLIGHT, facts={"kv_blocks": 10})) is None
    assert compute(src([])) is None


def test_dev_share_sums_the_listed_ops_that_hold_the_name():
    compute = load("ssm.dev_share").compute
    assert compute(src(FLIGHT)) == pytest.approx(100 * 0.362 / 3.0)
    assert compute(src(FLIGHT, ops=[OPS[0], OPS[4]])) is None
    # an op that merely TAKES the kernel's result does not count
    taker = ["%fusion.9 = f32[16] fusion(%mamba2_decode_update_l0x5_d16.3)",
             0.5]
    assert compute(src(FLIGHT, ops=[taker])) is None


def test_decode_roofline_holds_each_launch_against_its_own_work():
    compute = load("ssm.decode_roofline_share").compute
    # listed: d16 in both runs (9 layers x 3,000 rows), m2048's first run
    # only (5 layers x 10 rows); what is not listed adds neither
    rows = 9 * 300 * 10 + 5 * 10
    least = ssm_costs.update_bytes(rows, H, P, N) / 819e9
    assert compute(src(FLIGHT)) == pytest.approx(100 * least / 0.362)
    assert compute(src(FLIGHT)) < 100
    assert compute(src(FLIGHT, ops=[OPS[0]])) is None
    assert compute(src(FLIGHT, facts={"kv_blocks": 10})) is None
    # records without the field (the parent's): nothing to hold against
    bare = [{"t": 101.0, "decode_rows": 4}]
    assert compute(src(bare)) is None


def test_costs_against_a_hand_count():
    """One row, one layer, at the published sizes: the state is 128 x 64 x
    128 float32 = 4 MiB, read and written once."""
    state = 128 * 64 * 128 * 4
    assert state == 4 * 1024 * 1024
    assert ssm_costs.update_ops(1, H, P, N) == 5 * 1048576
    assert ssm_costs.update_bytes(1, H, P, N) == (
        2 * state + (8192 + 8192 + 128 + 128) * 4 + 8192 * 4)
    # 16 rows in 9 layers: 1.21 GB, 1.5 ms at 819 GB/s; the arithmetic is
    # a thousandth of that at 197 TFLOP/s: the update is bytes
    b = ssm_costs.update_bytes(16 * 9, H, P, N)
    assert b == 144 * ssm_costs.update_bytes(1, H, P, N)
    assert ssm_costs.roofline_seconds(
        ssm_costs.update_ops(144, H, P, N), b, 197e12, 819e9) == b / 819e9
    # a 2,048-token chunk in one layer: inside the blocks 2 x 128 x (128 +
    # 8192) a token, between them 4 x 1,048,576 a token
    assert ssm_costs.scan_ops(2048, H, P, N) == 2048 * (
        2 * 128 * 128 + 2 * 128 * 8192 + 4 * 1048576)
    assert ssm_costs.scan_bytes(2048, 1, H, P, N) == 2048 * (
        (8192 + 256 + 128) * 2 + 8192 * 4) + 2 * state
