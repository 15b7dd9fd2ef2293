"""Window and percentile arithmetic on synthetic streams, and the schedule
as a pure function of the seed."""

import json
import math
import os

import pytest

import loadgen
from loadgen import INF, Run, Stream, percentile, summarize

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def stream(idx, start, chunks, *, phase="window", max_tokens=None,
           finished=True, error=None, completion=None):
    n = max_tokens if max_tokens is not None else len(chunks)
    return Stream(idx, phase, n, 10, start_t=start, sent_t=start + 0.001,
                  chunk_t=list(chunks), finished=finished, error=error,
                  completion_tokens=(completion if completion is not None
                                     else (n if finished else None)))


def test_percentile_interpolates_like_numpy():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 90) == pytest.approx(4.6)
    assert percentile([7.0], 95) == 7.0
    assert math.isnan(percentile([], 50))


def test_percentile_reaches_failures_only_at_the_tail():
    xs = [0.1] * 9 + [INF]
    assert percentile(xs, 50) == 0.1
    assert percentile(xs, 90) == INF      # rank 8.1 leans on the failure


def test_ttft_counts_from_the_due_instant_not_the_send():
    s = stream(0, 100.0, [100.5, 100.6])
    s.sent_t = 100.3                      # the generator ran 0.3 s late
    assert s.ttft == pytest.approx(0.5)


def test_failures_are_infinite_and_counted():
    ok = stream(0, 10.0, [10.2, 10.3])
    http = stream(1, 10.0, [], error="http 500", finished=False)
    short = stream(2, 10.0, [10.1], max_tokens=5, completion=1)
    cut_before_first = stream(3, 19.0, [], finished=False)
    cut_after_first = stream(4, 19.0, [19.5], max_tokens=50, finished=False)
    run = Run("open", [ok, http, short, cut_before_first, cut_after_first],
              (10.0, 20.0), (0.0, 10.0), [], {})
    out = summarize(run)
    assert out["attempted"] == 5 and out["failed"] == 3
    assert sorted(out["ttft_s"])[:2] == pytest.approx([0.2, 0.5])
    assert out["ttft_s"].count(INF) == 3
    assert out["streams_wrong"] == 2      # the cut ones are not wrong


def test_one_gap_per_chunk_and_only_gaps_that_end_in_the_window():
    a = stream(0, 9.0, [9.5, 9.9, 10.1, 10.4], phase="ramp")
    b = stream(1, 19.0, [19.8, 19.9, 20.2], finished=False, max_tokens=9)
    run = Run("open", [a, b], (10.0, 20.0), (0.0, 10.0), [], {})
    out = summarize(run)
    # a: 9.9→10.1 and 10.1→10.4 end inside; 9.5→9.9 does not.
    # b: 19.8→19.9 ends inside; 19.9→20.2 does not.
    assert sorted(out["gaps_s"]) == pytest.approx([0.1, 0.2, 0.3])
    assert out["chunks_in_window"] == 4   # 10.1 10.4 19.8 19.9
    assert out["tokens_per_chunk"] == 1.0
    assert out["tokens_in_window"] == 4.0
    assert out["attempted"] == 1          # only b was due inside


def test_tokens_follow_usage_when_chunks_pack_several():
    a = stream(0, 10.0, [10.1, 10.2], max_tokens=6, completion=6)
    run = Run("open", [a], (10.0, 20.0), (0.0, 10.0), [], {})
    out = summarize(run)
    assert out["tokens_per_chunk"] == 3.0 and out["tokens_in_window"] == 6.0


@pytest.mark.parametrize("name", ["chat-steady", "chat-steady-tiny"])
def test_open_schedule_is_fixed_and_the_seed_draws_the_contents(name):
    m = mix(name)
    a = loadgen.open_schedule(m, 2.25, 48)
    assert a == loadgen.open_schedule(m, 2.25, 48)
    win = [r for r in a if r.phase == "window"]
    assert len(win) == round(2.25 * 48)
    ramp = float(m["ramp_s"])
    assert all(ramp - 1e-9 <= r.due_s < ramp + 48 for r in win)
    assert [r.phase for r in a] == sorted(
        (r.phase for r in a), key=["ramp", "window", "tail"].index)
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    lo, hi = m["prompt_tokens"]["min"], m["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_len <= hi for r in a)
    # the ramp is the end of the window's own cycle, the tail its start
    n_ramp = sum(1 for r in a if r.phase == "ramp")
    assert [r.prompt_len for r in a[:n_ramp]] == [
        r.prompt_len for r in win[-n_ramp:]]
    big = 2_300_000_011                   # the driver's seeds are large
    vocab = tuple(m["vocab"])
    n = win[0].prompt_len
    ids = loadgen.prompt_ids(n, win[0].idx, big, vocab)
    assert len(ids) == n
    assert all(vocab[0] <= t < vocab[1] for t in ids)
    assert ids == loadgen.prompt_ids(n, win[0].idx, big, vocab)
    assert ids != loadgen.prompt_ids(n, win[0].idx, 7, vocab)
    assert ids != loadgen.prompt_ids(n, win[1].idx, big, vocab)


def test_set_up_traffic_shares_no_block_with_the_window():
    """The rehearsal replays the window's sizes with ids of another stream,
    and the probe and the shape warm-up have theirs, even where ``--seed``
    is the number they are drawn from: nothing set-up sent may be found in
    the prefix cache by the window (16-token blocks)."""
    vocab, n = (10, 30000), 640

    def blocks(seed, stream, serial=0):
        ids = loadgen.prompt_ids(n, serial, seed, vocab, stream)
        return {tuple(ids[i:i + 16]) for i in range(0, n, 16)}

    for seed in (1, 777, 20240924, 2_300_000_011):
        window = blocks(seed, loadgen.WINDOW)
        for other_seed, stream in ((seed, loadgen.REHEARSAL),
                                   (seed, loadgen.REHEARSAL + 1),
                                   (777, loadgen.WARMUP),
                                   (20240924, loadgen.PROBE)):
            assert not window & blocks(other_seed, stream)


def test_closed_pool_is_fixed():
    m = mix("decode-saturated-tiny")
    a = loadgen.closed_pool(m, 8)
    assert a == loadgen.closed_pool(m, 8)
    assert len(a) == 8 * m["pool_per_client"]
    assert {r.max_tokens for r in a} == {32}
    assert all(32 <= r.prompt_len <= 128 for r in a)
    assert len({r.prompt_len for r in a}) > 30


def test_an_arrival_process_the_generator_lacks_is_an_error():
    m = dict(mix("chat-steady"), arrivals={"process": "gamma", "cv": 2.5})
    with pytest.raises(ValueError, match="arrival process"):
        loadgen.open_schedule(m, 2.0, 20)
