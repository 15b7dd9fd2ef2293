"""The readers of the engine loop's phase clock (ISSUE 42), on hand-made
flight records and ``/metrics`` texts."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARES = {"idle": ("idle", "blocked"), "plan": ("plan",), "build": ("build",),
          "dispatch": ("put", "dispatch", "sample"),
          "device_wait": ("device_wait",),
          "lag": ("lag",), "commit": ("commit",), "record": ("record",)}


def compute(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


def Src(flight=(), before="", after=""):
    from sources import Sources

    return Sources(client={}, flight=list(flight),
                   worker_metrics=(before, after), frontend_metrics=("", ""),
                   log="", facts={})


def rec(kind, phases, **fields):
    return dict(kind=kind, period_ms=sum(phases.values()), phases=phases,
                **fields)


def mixed(period, bucket, **phases):
    """A ragged record of ``bucket`` tokens whose period is ``period``."""
    phases["device_wait"] = period - sum(phases.values())
    return rec("ragged", phases, decode_rows=8, chunk_tokens=bucket - 16,
               padded_tokens=8)


STEPS = [
    rec("ragged", {"idle": 40.0, "plan": 1.0, "build": 2.0, "dispatch": 3.0,
                   "device_wait": 50.0, "lag": 1.0, "commit": 2.0,
                   "record": 1.0}, decode_rows=2, chunk_tokens=200,
        padded_tokens=54),
    rec("decode_pipe", {"build": 2.0, "put": 1.5, "dispatch": 1.0,
                        "sample": 0.5, "device_wait": 4.0, "lag": 0.5,
                        "commit": 1.0, "record": 0.5, "other": 1.0},
        decode_rows=8),
    rec("decode_pipe", {"build": 2.0, "dispatch": 2.0, "device_wait": 7.0,
                        "commit": 1.0, "record": 1.0}, decode_rows=8),
    rec("empty", {"plan": 0.5, "blocked": 50.0, "record": 0.5}),
    rec("decode_pipe", {"idle": 600.0, "plan": 1.0, "build": 2.0,
                        "dispatch": 2.0, "commit": 1.0}, decode_rows=1),
]
#: what the parent's side hands the readers: records without the clock
PARENT = [{k: v for k, v in s.items() if k not in ("period_ms", "phases")}
          for s in STEPS]


def test_the_eight_shares_and_other_sum_to_100():
    shares = {n: compute(f"loop.{n}_share")(Src(STEPS)) for n in SHARES}
    period = sum(s["period_ms"] for s in STEPS)
    for n, phases in SHARES.items():
        want = sum(s["phases"].get(p, 0.0) for s in STEPS for p in phases)
        assert shares[n] == pytest.approx(100.0 * want / period), n
    other = 100.0 * sum(s["phases"].get("other", 0.0) for s in STEPS) / period
    assert other > 0
    assert sum(shares.values()) + other == pytest.approx(100.0)
    # ``empty`` records count: their ``blocked`` is in the idle share
    assert shares["idle"] == pytest.approx(100.0 * 690.0 / period)


@pytest.mark.parametrize("name", [f"loop.{n}_share" for n in SHARES]
                         + ["step.decode_period_ms",
                            "step.top_bucket_period_ms"])
def test_records_without_phases_read_none(name):
    assert compute(name)(Src(PARENT)) is None
    assert compute(name)(Src([])) is None


def test_decode_period_is_the_median_of_steps_that_held_no_idle():
    # 12.0 and 13.0; the 606 ms one waited for a request, the ragged and
    # empty ones are no pipelined steps
    assert compute("step.decode_period_ms")(Src(STEPS)) == 12.5
    only_idle = [STEPS[4]]
    assert compute("step.decode_period_ms")(Src(only_idle)) is None


def test_top_bucket_period_picks_the_largest_bucket_and_needs_five():
    read = compute("step.top_bucket_period_ms")
    small = [mixed(20.0 + i, 256, build=1.0) for i in range(9)]
    large = [mixed(p, 2048, build=2.0, commit=1.0)
             for p in (101.0, 99.0, 100.0, 250.0, 98.0)]
    assert read(Src(small + large)) == 100.0
    # a fifth that waited for a request first does not count: four left
    waited = dict(large[3], phases=dict(large[3]["phases"], idle=150.0))
    assert read(Src(small + large[:3] + [waited] + large[4:])) is None
    assert read(Src(small + large[:4])) is None
    # without the large ones the largest bucket is the small one
    assert read(Src(small)) == 24.0
    # a pipelined step has no bucket of its own and is never the top one
    assert read(Src(small + [rec("decode_pipe", {"build": 1.0},
                                 decode_rows=4096)])) == 24.0


BEFORE = """# TYPE dynamo_tenant_queue_wait_seconds_total counter
dynamo_tenant_queue_wait_seconds_total{class="standard",tenant="a"} 1.5
dynamo_tenant_queue_wait_seconds_total{class="batch",tenant="b"} 0.5
# TYPE dynamo_tenant_queue_wait_count counter
dynamo_tenant_queue_wait_count{class="standard",tenant="a"} 10
dynamo_tenant_queue_wait_count{class="batch",tenant="b"} 10
"""
AFTER = (BEFORE.replace("} 1.5", "} 1.9").replace("} 0.5", "} 0.6")
         .replace('tenant="a"} 10', 'tenant="a"} 25')
         .replace('tenant="b"} 10', 'tenant="b"} 15'))


def test_queue_wait_mean_over_labels_and_none_when_nothing_was_admitted():
    read = compute("sched.queue_wait_mean_ms")
    # (0.4 + 0.1) s over (15 + 5) admissions
    assert read(Src(before=BEFORE, after=AFTER)) == pytest.approx(25.0)
    # a label that first appears inside the window counts from 0
    assert read(Src(before="", after=BEFORE)) == pytest.approx(100.0)
    assert read(Src(before=BEFORE, after=BEFORE)) is None
    assert read(Src(before="", after="")) is None
