"""The reducer's interval arithmetic on synthetic events, and the whole
reducer on a small trace recorded on the chip."""

import glob
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_self_time_with_nesting():
    # a `while` of 100 holding two body ops (30, 20), then a lone op after a
    # gap of 50, then two overlapping ops (the overlap goes to the later one,
    # so self times add up to the busy time)
    ev = sorted([(0, 100, "while"), (10, 40, "fusion.1"), (50, 70, "kernel"),
                 (150, 160, "copy"), (200, 230, "a"), (220, 250, "b")],
                key=lambda x: (x[0], -x[1]))
    merged, by_name = tr.union_and_self(ev)
    assert merged == [[0, 100], [150, 160], [200, 250]]
    assert by_name == {"while": 50, "fusion.1": 30, "kernel": 20,
                       "copy": 10, "a": 20, "b": 30}
    assert sum(b - a for a, b in merged) == 160 == sum(by_name.values())


def test_idle_gaps_are_tagged_by_the_host_annotation_they_fall_in():
    merged = [[10, 20], [60, 70], [75, 100]]
    notes = [(55, 72, "dynamo.ragged_step")]
    gaps = tr.idle_gaps(merged, notes, 0, 100, 5)
    # 20→60 (40, midpoint 40: outside), 0→10 (10), 70→75 (5, midpoint 72:
    # outside the annotation that ended at 72)
    assert gaps[0] == ["between steps", 40 / 1e9]
    assert [g[1] * 1e9 for g in gaps] == [40, 10, 5]
    inside = tr.idle_gaps([[0, 50], [70, 100]],
                          [(40, 80, "dynamo.decode_pipeline")], 0, 100, 5)
    assert inside == [["inside dynamo.decode_pipeline", 20 / 1e9]]


def test_collective_names():
    assert tr.is_collective("all-reduce.3")
    assert tr.is_collective("all-gather-start.1")
    assert not tr.is_collective("fusion.12")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="not in peaks.json"):
        tr.peaks_for("TPU v9 imaginary")
    assert tr.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_chip_trace_gives_the_numbers_found_by_hand():
    found = glob.glob(os.path.join(HERE, "recorded_trace", "*.xplane.pb"))
    assert found, "the recorded chip trace is missing"
    with open(os.path.join(HERE, "recorded_trace", "by_hand.json")) as f:
        want = json.load(f)
    got = tr.reduce_trace(found[0], want["kind"])
    assert sorted(got["devices"]) == want["devices"]
    dev = got["devices"][got["first_device"]]
    assert dev["events"] == want["events"]
    # the profile's own unit is the picosecond; ProfileData hands out ns
    assert dev["busy_s"] == pytest.approx(want["busy_s"], rel=1e-5)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-5)
    # tracing was on for longer than the ops cover: the edges are idle time
    asked = tr.reduce_trace(found[0], want["kind"], asked_s=0.4)
    assert asked["window_s"] == 0.4
    assert asked["devices"][asked["first_device"]]["idle_share"] == \
        pytest.approx(1 - want["busy_s"] / 0.4, rel=1e-5)
    assert asked["breakdown"]["idle_gaps"][0][1] == pytest.approx(
        0.4 - want["window_s"], rel=1e-4)
    assert dev["kernel_s"] == pytest.approx(want["kernel_s"], rel=1e-5)
    assert dev["collective_s"] == 0.0 and got["kernel_on_device"]
    assert got["steps_total"] == want["steps"]
    assert got["annotations"] == want["annotations"]
    ops = got["breakdown"]["device_ops"]
    assert ops[0][0].startswith(want["top_op"] + " = ")
    assert ops[0][1] == pytest.approx(want["kernel_s"], rel=1e-5)
    assert len(ops) == 10 and len(got["breakdown"]["idle_gaps"]) == 5
    assert got["breakdown"]["idle_gaps"][0][0] == "inside dynamo.ragged_step"


def test_the_kernel_is_the_op_named_so_not_one_that_reads_it():
    kernel = ("%ragged_paged_attention.7 = bf16[16,32,128]{2,1,0} "
              "custom-call(s32[8,3]{1,0} %get-tuple-element.705)")
    reader = ("%slice.70 = bf16[16,32,128]{2,1,0} slice(bf16[24,32,128]"
              "{2,1,0} %ragged_paged_attention.7), slice={[0:16]}")
    assert tr.is_kernel(kernel) and not tr.is_kernel(reader)
    assert tr.op_name(reader) == "%slice.70"
    ops = tr.by_op({kernel: 5, kernel.replace("[16,", "[24,"): 7, reader: 1})
    assert ops["%ragged_paged_attention.7"][0] == 12
    assert "[24," in ops["%ragged_paged_attention.7"][1]
