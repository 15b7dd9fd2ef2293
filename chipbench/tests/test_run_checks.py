"""The parts of ``run.py`` that decide ``correct``, describe the traced slice
or count ``setup_s``, on synthetic records."""

from types import SimpleNamespace

import pytest

import run


def probe(logprobs, top2_gap=None):
    return SimpleNamespace(logprobs=list(logprobs),
                           top2_gap=list(top2_gap or [1.0] * len(logprobs)))


def test_probe_repeats_needs_every_position_within_the_tolerance():
    ref = [-1.0 - 0.1 * i for i in range(run.PROBE_OUT)]
    near = [x + 0.05 for x in ref]
    out = run.probes_agree([probe(ref), probe(near), probe(ref)])
    assert out["ok"] and out["positions_agreeing"] == run.PROBE_OUT
    assert abs(out["max_logprob_gap"] - 0.05) < 1e-9
    off = list(ref)
    off[9] -= 0.5                         # no near-tie anywhere: a fault
    out = run.probes_agree([probe(ref), probe(ref), probe(off)])
    assert not out["ok"] and out["positions_agreeing"] == 9


def test_probe_may_part_only_behind_a_near_tie():
    ref = [-1.0] * run.PROBE_OUT
    gaps = [1.0] * run.PROBE_OUT
    gaps[5] = 0.01                        # the top two all but tie at 5
    flipped = ref[:6] + [-3.0] * (run.PROBE_OUT - 6)
    assert run.probes_agree([probe(ref, gaps), probe(flipped)])["ok"]
    early = ref[:4] + [-3.0] * (run.PROBE_OUT - 4)    # parts before the tie
    assert not run.probes_agree([probe(ref, gaps), probe(early)])["ok"]
    short = probe(ref[:10], gaps)         # fewer logprobs than asked for
    assert not run.probes_agree([probe(ref, gaps), short])["ok"]


def test_slice_is_set_against_the_window():
    flight = [{"t": 100.0 + i, "chunk_tokens": 100 if i >= 8 else 10,
               "decode_rows": 4} for i in range(10)]
    out = run.slice_against_window(flight, 108.0, 100.0, 110.0)
    assert out["slice_s"] == 2.0
    assert out["slice"]["prefill_tokens_per_s"] == 100.0
    assert out["window"]["prefill_tokens_per_s"] == 28.0
    assert out["slice_over_window"]["decode_tokens_per_s"] == 1.0
    assert out["slice_over_window"]["steps_per_s"] == 1.0


@pytest.mark.parametrize("system_s, rehearsal_s", [
    (53.0, 0.0),        # a warm cache: no rehearsal, the count as it was
    (53.0, 58.2),       # the same set-up on a side whose harness rehearsed
    (241.0, 58.2),      # a checkout's cold first run, which always rehearses
])
def test_setup_counts_the_system_not_the_rehearsal(system_s, rehearsal_s):
    t_start = 1000.0
    window_start = t_start + system_s + rehearsal_s
    got = run.setup_seconds(window_start, t_start, rehearsal_s)
    assert got == pytest.approx(system_s)
    if not rehearsal_s:
        assert got == window_start - t_start
