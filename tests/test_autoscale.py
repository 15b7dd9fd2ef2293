"""Closed-loop SLA autoscaler (docs/autoscaling.md): SLO spec, fused
observation feed, controller decision logic (cooldown / readiness gate /
reactive terms), drain-safe operator scale-down, and the planner-loop
telemetry the ISSUE 6 satellites pinned.

All loop tests are deterministic: fake metrics sources, fake clocks, and
(for the operator) real subprocesses with scripted SIGTERM behavior."""

import asyncio
import json
import os
import signal
import sys
import time

import msgpack
import pytest

from benchmarks.client import Mix
from dynamo_tpu.autoscale import (
    AutoscaleController, ClassTtftTracker, FusedObservation,
    ObservationFuser, SloConfig, histogram_p95, make_planner,
    plane_readiness,
)
from dynamo_tpu.autoscale.observe import TTFT_CLASS_METRIC
from dynamo_tpu.deploy.operator import ProcessOperator
from dynamo_tpu.planner.perf_interpolation import PerfInterpolator
from dynamo_tpu.planner.planner_core import (
    Decision, Observation, PlannerRunner,
)
from dynamo_tpu.planner.prometheus import (
    PrometheusMetricsSource, parse_prometheus_text,
)
from dynamo_tpu.runtime.config import ConfigError

pytestmark = pytest.mark.anyio

# single-replica profiling sweeps (same shape as tests/test_planner.py):
# at the default interactive SLO (TTFT 200ms / ITL 20ms) one replica holds
# 1.0 req/s of prefill and ~2235 decode tok/s
PREFILL_SWEEP = [(0.5, 80), (1.0, 100), (2.0, 150), (4.0, 300), (8.0, 900)]
DECODE_SWEEP = [(500, 8), (1000, 12), (2000, 18), (4000, 35), (8000, 80)]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class FakeFuser:
    """async () -> FusedObservation from a scripted queue (last repeats)."""

    def __init__(self, *fused):
        self.queue = list(fused)
        self.scrape_failures = 0

    def push(self, f: FusedObservation) -> None:
        self.queue.append(f)

    async def __call__(self) -> FusedObservation:
        if len(self.queue) > 1:
            return self.queue.pop(0)
        return self.queue[0]


class FakeConnector:
    def __init__(self):
        self.applied: list[Decision] = []

    async def apply(self, decision: Decision) -> None:
        self.applied.append(decision)


def obs(rate: float, **kw) -> FusedObservation:
    return FusedObservation(
        observation=Observation(request_rate=rate, isl=1000, osl=250, **kw))


def controller(slo=None, *, readiness=None, clock=None,
               **planner_overrides):
    slo = slo or SloConfig(cooldown_up_s=10.0, cooldown_down_s=30.0)
    planner_overrides.setdefault("predictor", "constant")
    planner = make_planner(slo, PerfInterpolator(PREFILL_SWEEP),
                           PerfInterpolator(DECODE_SWEEP),
                           **planner_overrides)
    conn = FakeConnector()
    fuser = FakeFuser(obs(0.1))
    ctl = AutoscaleController(slo, planner, fuser, conn,
                              readiness=readiness,
                              now_fn=clock or FakeClock())
    return ctl, conn, fuser


# ------------------------------------------------------------ SLO config

def test_slo_config_env_loading():
    cfg = SloConfig.load(env={
        "DYN_SLO_INTERACTIVE_TTFT_P95_MS": "120",
        "DYN_SLO_BATCH_TTFT_P95_MS": "9000",
        "DYN_SLO_STANDARD_TTFT_P95_MS": "",  # empty CLEARS the default
        "DYN_SLO_MAX_REPLICAS": "5",
        "DYN_SLO_COOLDOWN_UP_S": "3",
        "DYN_SLO_PREDICTOR": "arima",
    })
    assert cfg.slo_for("interactive").ttft_p95_ms == 120.0
    assert cfg.slo_for("batch").ttft_p95_ms == 9000.0
    assert cfg.slo_for("standard").ttft_p95_ms is None
    assert cfg.max_replicas == 5 and cfg.cooldown_up_s == 3.0
    assert cfg.predictor == "arima"
    # the governing class parameterizes the planner inversion
    assert cfg.governing.ttft_p95_ms == 120.0

    with pytest.raises(ConfigError):
        SloConfig.load(env={"DYN_SLO_MIN_REPLICAS": "nope"})
    with pytest.raises(ConfigError):
        SloConfig(min_replicas=5, max_replicas=2)
    with pytest.raises(ConfigError):
        SloConfig(governing_class="platinum")
    with pytest.raises(ConfigError):
        SloConfig(predictor="oracle")


# --------------------------------------------------- per-class TTFT feed

def _exposition(per_class: dict) -> str:
    lines = []
    for cls, buckets in per_class.items():
        for le, cum in buckets.items():
            le_s = "+Inf" if le == float("inf") else str(le)
            lines.append(
                f'{TTFT_CLASS_METRIC}_bucket{{qos="{cls}",le="{le_s}"}} '
                f"{cum}")
    return "\n".join(lines)


def test_histogram_p95_interpolates_crossing_bucket():
    inf = float("inf")
    # crossing inside [0.1, 0.5): target 95 of 100, 60 below 0.1
    assert histogram_p95({0.1: 60, 0.5: 90, 1.0: 99, inf: 100}) == \
        pytest.approx(1.0 - (4 / 9) * 0.5)
    # everything in the first bucket: linear from 0
    assert histogram_p95({0.1: 100, inf: 100}) == pytest.approx(0.095)
    # p95 lands in the +Inf tail: best lower bound is the last finite edge
    assert histogram_p95({0.1: 60, 0.5: 90, inf: 100}) == 0.5
    assert histogram_p95({inf: 0}) is None  # idle interval
    assert histogram_p95({0.1: 5}) is None  # malformed: no +Inf


def test_class_ttft_tracker_interval_p95_and_reset():
    inf = float("inf")
    tr = ClassTtftTracker()
    assert tr.feed(None) == {}
    assert tr.feed(_exposition(
        {"interactive": {0.1: 0, 0.2: 0, inf: 0}})) == {}  # first scrape
    out = tr.feed(_exposition(
        {"interactive": {0.1: 10, 0.2: 19, inf: 20},
         "batch": {0.1: 0, 0.2: 0, inf: 0}}))
    # 19/20 cumulative at 0.2 → p95 target 19 lands exactly on 0.2s
    assert out == {"interactive": 200.0}  # idle batch class omitted
    # frontend restart: counters go BACKWARD → per-bucket deltas clamp at
    # 0 → idle interval, not a poisoned one
    assert tr.feed(_exposition(
        {"interactive": {0.1: 1, 0.2: 2, inf: 2}})) == {}


async def test_fuser_tolerates_frontend_scrape_failure():
    class DeadFrontend:
        async def __call__(self):
            raise OSError("connection refused")

    class Agg:
        def aggregate(self):
            return {"requests_waiting": 17, "requests_active": 3,
                    "workers": 2, "total_slots": 8}

    fuser = ObservationFuser(DeadFrontend(), Agg())
    fused = await fuser()
    assert fused.frontend_down and fused.observation is None
    assert fused.queue_depth == 17 and fused.workers == 2
    assert fuser.scrape_failures == 1


async def test_fuser_threads_queue_depth_into_observation():
    class Frontend:
        last_text = None

        async def __call__(self):
            return Observation(request_rate=2.0, isl=100, osl=10)

    class Agg:
        def aggregate(self):
            return {"requests_waiting": 9, "requests_active": 1,
                    "workers": 1, "total_slots": 4}

    fused = await ObservationFuser(Frontend(), Agg())()
    assert fused.observation.queue_depth == 9


# --------------------------------------------------------- controller core

async def test_scale_up_on_predicted_ramp():
    ctl, conn, fuser = controller()
    fuser.push(obs(9.0))
    r1 = await ctl.tick()  # primer obs (rate 0.1): hold at (1,1)
    assert r1.direction == "hold" and not conn.applied
    r2 = await ctl.tick()
    assert r2.applied and r2.direction == "up" and r2.reason == "predicted"
    assert conn.applied[-1] == Decision(4, 2)  # 9 req/s over the sweeps
    assert ctl.scale_ups == 1 and ctl.applied == Decision(4, 2)


async def test_cooldown_suppresses_flapping():
    clock = FakeClock()
    ctl, conn, fuser = controller(clock=clock, scale_down_patience=1)
    fuser.queue = [obs(9.0)]
    await ctl.tick()  # up to (4,2) at t=0
    assert ctl.scale_ups == 1
    # demand oscillates every tick, 1s apart: inside both cooldown
    # windows nothing further may actuate
    for i in range(8):
        clock.t += 1.0
        fuser.queue = [obs(0.2 if i % 2 == 0 else 9.0)]
        await ctl.tick()
    assert len(conn.applied) == 1  # the initial up only
    assert ctl.held_for_cooldown > 0
    # past the down-cooldown with demand steadily low → one scale-down
    clock.t += 60.0
    fuser.queue = [obs(0.2)]
    r = await ctl.tick()
    assert r.applied and r.direction == "down"
    assert ctl.scale_downs == 1 and ctl.applied == Decision(1, 1)


async def test_readiness_gate_defers_scale_up():
    clock = FakeClock()
    ready = {"decode": 1, "prefill": 1}

    async def readiness():
        return dict(ready)

    # max_replicas=4 pins the prefill fleet so the decode gate is isolated
    ctl, conn, fuser = controller(
        SloConfig(cooldown_up_s=10.0, cooldown_down_s=30.0, max_replicas=4),
        clock=clock, readiness=readiness)
    fuser.queue = [obs(9.0)]
    await ctl.tick()  # up to (4,2); replicas now materializing
    assert ctl.applied == Decision(4, 2)
    # demand rises further while ready(1) < applied(2): the controller
    # must NOT stack another decode scale-up onto a fleet still starting
    clock.t += 60.0
    fuser.queue = [obs(18.0)]  # wants decode 3
    r = await ctl.tick()
    assert r.reason == "deferred_unready"
    assert ctl.applied.decode_replicas == 2
    assert ctl.deferred_for_readiness == 1
    # capacity materializes → the deferred step is taken
    ready["decode"] = 2
    clock.t += 60.0
    r2 = await ctl.tick()
    assert r2.applied and ctl.applied.decode_replicas >= 3


async def test_backlog_scales_reactively_with_frontend_down():
    """A dead frontend scrape must not blind the loop: worker queue depth
    alone forces scale-up (the reactive half of the feed)."""
    ctl, conn, fuser = controller(
        SloConfig(cooldown_up_s=0.0, backlog_per_replica=8.0))
    fuser.queue = [FusedObservation(observation=None, frontend_down=True,
                                    queue_depth=40)]
    r = await ctl.tick()
    assert r.applied and r.reason == "backlog"
    assert ctl.applied.decode_replicas == 5  # ceil(40/8)


async def test_slo_breach_adds_replica():
    ctl, conn, fuser = controller(SloConfig(cooldown_up_s=0.0))
    fused = obs(0.1)
    fused.ttft_p95_ms = {"interactive": 500.0}  # target 200ms → breach
    fuser.queue = [fused]
    r = await ctl.tick()
    assert r.applied and r.reason == "slo_breach"
    assert not r.breaches["interactive"]["ok"]
    assert ctl.applied.decode_replicas == 2  # applied+1, not a jump
    # TTFT is prefill-bound in disagg: a scalable prefill fleet steps too
    assert ctl.applied.prefill_replicas == 2

    # with the prefill dimension pinned (aggregated fleet), only decode
    ctl2, _, fuser2 = controller(SloConfig(cooldown_up_s=0.0),
                                 min_prefill_replicas=1,
                                 max_prefill_replicas=1)
    f2 = obs(0.1)
    f2.ttft_p95_ms = {"interactive": 500.0}
    fuser2.queue = [f2]
    await ctl2.tick()
    assert ctl2.applied.prefill_replicas == 1
    assert ctl2.applied.decode_replicas == 2


async def test_scale_bounds_clamp():
    slo = SloConfig(cooldown_up_s=0.0, max_replicas=3)
    ctl, conn, fuser = controller(slo)
    fuser.queue = [FusedObservation(observation=None, queue_depth=1000)]
    await ctl.tick()
    assert ctl.applied.decode_replicas == 3


async def test_status_published_to_plane():
    class PlaneStub:
        def __init__(self):
            self.put = {}

        async def kv_put(self, key, value, lease_id=None):
            self.put[key] = value

    plane = PlaneStub()
    slo = SloConfig(cooldown_up_s=0.0)
    planner = make_planner(slo, PerfInterpolator(PREFILL_SWEEP),
                           PerfInterpolator(DECODE_SWEEP),
                           predictor="constant")
    ctl = AutoscaleController(slo, planner, FakeFuser(obs(9.0)),
                              FakeConnector(), plane=plane,
                              namespace="t", now_fn=FakeClock())
    await ctl.tick()
    status = json.loads(plane.put["public/autoscale/t/status"])
    assert status["desired"] == {"prefill": 4, "decode": 2}
    assert status["lastDecision"]["direction"] == "up"
    assert status["counters"]["ticks"] == 1


async def test_plane_readiness_rolls_up_by_role():
    class PlaneStub:
        async def kv_get(self, key):
            return json.dumps({
                "services": {
                    "decode-a": {"plannerRole": "decode", "ready": 2},
                    "decode-b": {"plannerRole": "decode", "ready": 1},
                    "front": {"plannerRole": None, "ready": 1},
                },
                "drainSecondsTotal": 3.5,
            }).encode()

    out = await plane_readiness(PlaneStub(), "ns")
    assert out["decode"] == 3 and "front" not in out
    assert out["_drain_seconds_total"] == 3.5

    class EmptyPlane:
        async def kv_get(self, key):
            return None

    assert await plane_readiness(EmptyPlane()) is None


async def test_correction_runaway_does_not_pin_fleet_at_max():
    """Regression (found driving the live loop): an ITL target the engine
    can never meet per-replica (raw SLA 20 ms vs ~23 ms true ITL) grows
    the correction factor until the CORRECTED target falls below the
    profile's idle latency — max_load_under then answers 0 ("impossible")
    and the planner pinned the fleet at max through an entire load
    trough. Scale-out cannot improve per-replica latency, so the capacity
    lookup must fall back to the profile's most pessimistic measured
    point, not to max replicas."""
    slo = SloConfig(cooldown_up_s=0.0, cooldown_down_s=0.0, max_replicas=3)
    decode = PerfInterpolator([(24.0, 10.0), (48.0, 40.0), (96.0, 300.0)])
    planner = make_planner(slo, PerfInterpolator(PREFILL_SWEEP), decode,
                           predictor="constant", scale_down_patience=1)
    # trough traffic, engine ITL ~23 ms vs the 20 ms governing target:
    # the EMA drives d_correction well past 2 (corrected target < 10 ms)
    for _ in range(10):
        planner.observe(Observation(request_rate=1.0, isl=60, osl=24,
                                    ttft_ms=40.0, itl_ms=23.0))
    d = planner.compute()
    assert planner.d_correction_factor > 2.0  # runaway happened…
    # …but demand 24 tok/s against the 24 tok/s floor capacity = 1
    assert d.decode_replicas == 1

    # a RAW SLA below the profile floor still honestly pins to max
    # (ref behavior: test_impossible_sla_pins_to_max)
    hard = make_planner(slo, PerfInterpolator(PREFILL_SWEEP), decode,
                        predictor="constant", itl_sla_ms=5.0)
    hard.observe(Observation(request_rate=1.0, isl=60, osl=24))
    assert hard.compute().decode_replicas == 3


# ------------------------------------------------- PlannerRunner telemetry

async def test_planner_runner_tick_cadence_and_empty_ticks():
    calls = {"n": 0}

    async def source():
        calls["n"] += 1
        return None  # idle interval: no observation

    planner = make_planner(SloConfig(), PerfInterpolator(PREFILL_SWEEP),
                           PerfInterpolator(DECODE_SWEEP),
                           predictor="constant")
    conn = FakeConnector()
    runner = PlannerRunner(planner, source, conn, interval_s=0.01)
    await runner.start()
    await asyncio.sleep(0.15)
    await runner.stop()
    assert runner.ticks >= 3
    assert runner.ticks == calls["n"]
    assert runner.empty_ticks == runner.ticks  # every interval was idle
    assert not conn.applied  # an idle source must not actuate


async def test_planner_runner_survives_scrape_failures():
    state = {"n": 0}

    async def flaky_source():
        state["n"] += 1
        if state["n"] <= 2:
            raise OSError("scrape refused")
        return Observation(request_rate=9.0, isl=1000, osl=250)

    planner = make_planner(SloConfig(), PerfInterpolator(PREFILL_SWEEP),
                           PerfInterpolator(DECODE_SWEEP),
                           predictor="constant")
    conn = FakeConnector()
    runner = PlannerRunner(planner, flaky_source, conn, interval_s=0.01)
    await runner.start()
    for _ in range(100):
        if conn.applied:
            break
        await asyncio.sleep(0.01)
    await runner.stop()
    assert runner.tick_errors == 2  # both failures counted…
    assert conn.applied  # …and the loop went on to actuate


# ------------------------------------- prometheus counter-reset (satellite)

def _prom_text(finished, prompt, completion, lat_sum, lat_cnt,
               ttft_sum, ttft_cnt):
    return "\n".join([
        f"dynamo_llm_requests_finished_total {finished}",
        f"dynamo_llm_prompt_tokens_total {prompt}",
        f"dynamo_llm_completion_tokens_total {completion}",
        f"dynamo_http_request_duration_seconds_sum {lat_sum}",
        f"dynamo_http_request_duration_seconds_count {lat_cnt}",
        f"dynamo_http_time_to_first_token_seconds_sum {ttft_sum}",
        f"dynamo_http_time_to_first_token_seconds_count {ttft_cnt}",
    ])


async def test_counter_reset_does_not_poison_deltas():
    """Satellite bugfix: a frontend restart resets its counters; the delta
    source must skip that interval (flagging the reset) instead of feeding
    the predictor a negative or partial-window rate."""
    samples = []
    src = PrometheusMetricsSource("http://unused:0")

    async def fake_fetch():
        return parse_prometheus_text(samples.pop(0))

    src._fetch = fake_fetch
    samples.append(_prom_text(100, 50000, 10000, 100.0, 100, 10.0, 100))
    assert await src() is None  # first sample

    # frontend restarted: every counter is back near zero
    samples.append(_prom_text(3, 1500, 300, 3.0, 3, 0.3, 3))
    src._prev_t -= 10.0
    assert await src() is None  # reset interval skipped…
    assert src.resets == 1

    # …and the NEXT interval rebases cleanly on the fresh counters
    samples.append(_prom_text(23, 17500, 4300, 23.0, 23, 2.3, 23))
    src._prev_t -= 10.0
    o = await src()
    assert o is not None and o.request_rate == pytest.approx(2.0, abs=0.2)
    assert o.isl == pytest.approx(800.0)
    assert o.osl == pytest.approx(200.0)


# --------------------------------------------- operator: drain-safe scaling

# both workers touch READY_MARKER only AFTER installing their SIGTERM
# handler — the tests must not scale down while the child is still in
# interpreter startup (default SIGTERM disposition: die instantly)
GRACEFUL_WORKER = [sys.executable, "-c", """
import os, signal, sys, time
marker = os.environ["DRAIN_MARKER"]
def on_term(signum, frame):
    time.sleep(0.3)                       # "finish the in-flight stream"
    open(marker, "w").write("drained")
    sys.exit(0)
signal.signal(signal.SIGTERM, on_term)
open(os.environ["READY_MARKER"], "w").write("up")
while True:
    time.sleep(0.05)
"""]

STUBBORN_WORKER = [sys.executable, "-c", """
import os, signal, time
signal.signal(signal.SIGTERM, signal.SIG_IGN)
open(os.environ["READY_MARKER"], "w").write("up")
while True:
    time.sleep(0.05)
"""]


async def _await_file(path: str, timeout_s: float = 10.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        assert time.monotonic() < deadline, f"{path} never appeared"
        await asyncio.sleep(0.02)

SLEEPER = [sys.executable, "-c", "import time\nwhile True: time.sleep(0.2)"]


def write_spec(path, services: dict) -> None:
    import yaml

    doc = {"apiVersion": "dynamo.tpu/v1alpha1",
           "kind": "DynamoGraphDeployment",
           "metadata": {"name": "t"},
           "spec": {"services": services}}
    with open(path, "w") as f:
        yaml.safe_dump(doc, f)


def alive(op: ProcessOperator, svc: str) -> int:
    return sum(1 for r in op.replicas[svc] if r.proc.poll() is None)


async def test_drain_safe_scale_down_completes_in_flight(tmp_path):
    """Satellite bugfix regression: scale-down must SIGTERM + wait the
    drain window ASYNCHRONOUSLY — reconcile keeps ticking, and a victim
    that finishes its work inside the window is never SIGKILLed."""
    marker = str(tmp_path / "drained.txt")
    ready = str(tmp_path / "ready.txt")
    spec = str(tmp_path / "graph.yaml")
    env = {"DRAIN_MARKER": marker, "READY_MARKER": ready}
    write_spec(spec, {"w": {"replicas": 1, "command": GRACEFUL_WORKER,
                            "env": env}})
    op = ProcessOperator(spec, tick_s=0.05, drain_timeout=5.0)
    try:
        op.reconcile_once()
        assert alive(op, "w") == 1
        victim = op.replicas["w"][0].proc
        await _await_file(ready)  # SIGTERM handler installed

        write_spec(spec, {"w": {"replicas": 0, "command": GRACEFUL_WORKER,
                                "env": env}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        t0 = time.monotonic()
        op.reconcile_once()
        reconcile_took = time.monotonic() - t0
        # the old code blocked reconcile in proc.wait(timeout=10); the
        # fix returns immediately with the victim still draining
        assert reconcile_took < 0.25
        assert victim.poll() is None  # still finishing its stream
        assert len(op._draining["w"]) == 1
        status = json.load(open(spec + ".status.json"))
        assert status["services"]["w"]["draining"] == 1

        for _ in range(200):  # keep reconciling while the drain completes
            op.reconcile_once()
            if op.drains_completed == 1:
                break
            await asyncio.sleep(0.02)
        assert op.drains_completed == 1 and op.drains_killed == 0
        assert open(marker).read() == "drained"  # graceful, not SIGKILL
        assert op.drain_seconds_total > 0.0
    finally:
        await op.stop(drain=False)


async def test_stubborn_victim_killed_after_window(tmp_path):
    spec = str(tmp_path / "graph.yaml")
    ready = str(tmp_path / "ready.txt")
    env = {"READY_MARKER": ready}
    write_spec(spec, {"w": {"replicas": 1, "command": STUBBORN_WORKER,
                            "env": env}})
    op = ProcessOperator(spec, tick_s=0.05, drain_timeout=0.4)
    try:
        op.reconcile_once()
        await _await_file(ready)  # SIG_IGN installed
        write_spec(spec, {"w": {"replicas": 0, "command": STUBBORN_WORKER,
                                "env": env}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        assert len(op._draining["w"]) == 1
        for _ in range(200):
            op.reconcile_once()
            if op.drains_killed == 1:
                break
            await asyncio.sleep(0.02)
        assert op.drains_killed == 1 and op.drains_completed == 0
    finally:
        await op.stop(drain=False)


def test_drain_timeout_env_honored(tmp_path, monkeypatch):
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {"replicas": 0, "command": SLEEPER}})
    monkeypatch.setenv("DYN_DRAIN_TIMEOUT", "7.5")
    assert ProcessOperator(spec).drain_timeout == 7.5
    monkeypatch.setenv("DYN_DRAIN_TIMEOUT", "junk")
    with pytest.raises(ValueError):
        ProcessOperator(spec)


def test_status_file_written_atomically(tmp_path):
    """Satellite bugfix: status lands via temp file + os.replace, so a
    reader can never observe a torn JSON document."""
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {"replicas": 2, "command": SLEEPER}})
    op = ProcessOperator(spec, tick_s=0.05)
    try:
        real_replace, seen = os.replace, []

        def spying_replace(src, dst):
            # the temp file must already hold COMPLETE valid JSON when it
            # is atomically swapped into place
            seen.append(json.load(open(src)))
            real_replace(src, dst)

        os.replace = spying_replace
        try:
            op.reconcile_once()
        finally:
            os.replace = real_replace
        assert seen and seen[-1]["services"]["w"]["alive"] == 2
        assert not os.path.exists(spec + ".status.json.tmp")
        assert json.load(open(spec + ".status.json"))
    finally:
        op._scale_to(op.services["w"], 0)
        for r in op._draining["w"]:
            r.proc.kill()
            r.proc.wait()


async def test_victim_selection_fewest_inflight(tmp_path):
    """Scale-down victims: unregistered first, then fewest in-flight
    streams, newest-first on ties — shedding capacity disturbs the least
    work."""
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {"replicas": 3, "command": SLEEPER}})
    op = ProcessOperator(spec, tick_s=0.05, drain_timeout=2.0)
    try:
        op.reconcile_once()
        r0, r1, r2 = op.replicas["w"]
        # r0 carries 5 streams, r2 carries 1; r1 never registered (-1)
        op._registered_pods = {r0.pod_name: 100, r2.pod_name: 102}
        op._inflight_by_instance = {100: 5, 102: 1}

        write_spec(spec, {"w": {"replicas": 2, "command": SLEEPER}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        assert {r.pod_name for r in op.replicas["w"]} == \
            {r0.pod_name, r2.pod_name}  # the unregistered one went first

        write_spec(spec, {"w": {"replicas": 1, "command": SLEEPER}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        # the busy replica survives; the 1-stream one drains
        assert [r.pod_name for r in op.replicas["w"]] == [r0.pod_name]
    finally:
        await op.stop(drain=False)
        for rs in op._draining.values():
            for r in rs:
                r.proc.kill()


async def test_readiness_gate_counts_registered_only(tmp_path):
    """A planner-role replica counts as ready only once REGISTERED on the
    control plane (registration happens after AOT warmup, so 'registered'
    subsumes 'warm') — Popen returning is not capacity."""
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {"replicas": 2, "command": SLEEPER,
                            "plannerRole": "decode"}})

    class PlaneStub:  # only attached, never ticked (no start())
        pass

    op = ProcessOperator(spec, plane=PlaneStub(), tick_s=0.05)
    try:
        op._planner_target = {"decode": 2}
        op.reconcile_once()
        st = op._status()["services"]["w"]
        assert st["alive"] == 2 and st["ready"] == 0  # phantom capacity
        assert st["readinessGated"]

        op._registered_pods = {op.replicas["w"][0].pod_name: 7}
        assert op._status()["services"]["w"]["ready"] == 1
        op._registered_pods.update(
            {op.replicas["w"][1].pod_name: 8})
        assert op._status()["services"]["w"]["ready"] == 2
    finally:
        op.plane = None  # stop() must not touch the stub
        await op.stop(drain=False)
        for r in op.replicas["w"]:
            r.proc.kill()


async def test_refresh_observed_parses_registrations(tmp_path):
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {"replicas": 0, "command": SLEEPER,
                            "plannerRole": "decode"}})

    class PlaneStub:
        async def kv_get_prefix(self, prefix):
            assert prefix == "instances/"
            return {
                "instances/ns/w/gen:2a": msgpack.packb({
                    "namespace": "ns", "component": "w", "endpoint": "gen",
                    "instance_id": 42, "metadata": {"pod": "w-0-1"}}),
                "instances/ns/w/gen:2b": msgpack.packb({
                    "namespace": "ns", "component": "w", "endpoint": "gen",
                    "instance_id": 43, "metadata": {}}),  # no pod: ignored
                "instances/ns/w/gen:2c": b"not msgpack",  # tolerated
            }

    op = ProcessOperator(spec, plane=PlaneStub(), tick_s=0.05)
    await op._refresh_observed()
    assert op._registered_pods == {"w-0-1": 42}


# -------------------------------------------------- load-tool helpers

def test_mix_parser():
    import random

    m = Mix("interactive=0.5,batch=0.5")
    rng = random.Random(7)
    picks = [m.pick(rng) for _ in range(400)]
    assert 120 < picks.count("interactive") < 280  # both sides sampled
    assert set(picks) == {"interactive", "batch"}
    # bare names = uniform weights; empty = no header
    assert Mix("a,b").choices == [("a", 1.0), ("b", 1.0)]
    assert not Mix("") and Mix("").pick(rng) is None
    with pytest.raises(ValueError):
        Mix("a=x")
    with pytest.raises(ValueError):
        Mix("a=0,b=0")
    with pytest.raises(ValueError):
        Mix("a=-1")


def test_metrics_aggregator_expires_stale_workers():
    """A drained/crashed worker's last report must age out of the
    aggregate, or the autoscaler reads phantom backlog forever."""
    from dynamo_tpu.router.protocols import (
        ForwardPassMetrics, KvStats, SpecDecodeStats, WorkerStats,
    )
    from dynamo_tpu.router.publisher import MetricsAggregator

    agg = MetricsAggregator(plane=None, stale_after_s=0.05)
    m = ForwardPassMetrics(
        worker_stats=WorkerStats(request_active_slots=2,
                                 request_total_slots=4,
                                 num_requests_waiting=6),
        kv_stats=KvStats(), spec_decode_stats=SpecDecodeStats())
    agg.latest[1] = m
    agg._seen_at[1] = time.monotonic()
    assert agg.aggregate()["requests_waiting"] == 6
    assert agg.aggregate()["total_slots"] == 4
    agg._seen_at[1] = time.monotonic() - 1.0  # worker went silent
    assert agg.aggregate()["workers"] == 0
    assert agg.aggregate()["requests_waiting"] == 0


# ------------------------------------------------- the closed loop, end to end


async def test_closed_loop_scales_both_ways_without_losing_a_token(tmp_path,
                                                                   monkeypatch):
    """A REAL fleet: a control-plane hub, an in-process frontend, and mocker
    workers spawned as operator subprocesses (plannerRole decode,
    readiness-gated). The controller fuses frontend /metrics scrapes with
    worker ForwardPassMetrics, runs predictor + planner, and actuates
    through the VirtualConnector key the operator follows — while one
    diurnal sine of QoS-mixed traffic runs with seeded chaos dropping 2% of
    worker token frames. The loop scales up AND back down to the floor by
    itself, every request completes with exactly its tokens (drain +
    migration absorb scale-downs and chaos), batch traffic all completes
    and the backlog drains."""
    import math
    import random

    import aiohttp
    import numpy as np
    import yaml

    from benchmarks.client import make_prompt, qos_headers, stream_request
    from dynamo_tpu.autoscale import AutoscaleRunner
    from dynamo_tpu.autoscale.slo import ClassSlo
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.planner.virtual_connector import VirtualConnector
    from dynamo_tpu.router.publisher import MetricsAggregator
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.control_plane import ControlPlaneServer

    model, osl, duration_s, period = "autoscale-loop", 24, 40.0, 36.0
    # the sweeps tell the planner one replica holds ~2 req/s, so the
    # sine's 0.4 -> 4.0 req/s swing demands 1 -> 2(3) -> 1 replicas; real
    # worker capacity sits well above that, as a production loop is sized
    slo = SloConfig(
        class_slos={"interactive": ClassSlo(ttft_p95_ms=1500.0, itl_ms=40.0),
                    "standard": ClassSlo(ttft_p95_ms=6000.0, itl_ms=80.0),
                    "batch": ClassSlo()},
        min_replicas=1, max_replicas=3, cooldown_up_s=2.0,
        cooldown_down_s=8.0, adjustment_interval_s=1.0, predictor="arima",
        backlog_per_replica=3.0)
    server = ControlPlaneServer(port=0)
    addr = await server.start()
    monkeypatch.setenv("DYN_CONTROL_PLANE", addr)
    spec = str(tmp_path / "graph.yaml")
    with open(spec, "w") as f:
        yaml.safe_dump({
            "apiVersion": "dynamo.tpu/v1alpha1",
            "kind": "DynamoGraphDeployment",
            "metadata": {"name": model},
            "spec": {"services": {"decode": {
                "replicas": 1, "plannerRole": "decode",
                "command": [
                    sys.executable, "-m", "dynamo_tpu.mocker.main",
                    "--model", model, "--component", "mocker",
                    "--block-size", "4", "--num-gpu-blocks", "4096",
                    "--max-num-seqs", "4", "--speedup-ratio", "0.1",
                    "--migration-limit", "50"],
                "env": {"DYN_CONTROL_PLANE": addr,
                        "PYTHONPATH": os.pathsep.join(sys.path),
                        "JAX_PLATFORMS": "cpu",
                        # chaos lives in the WORKERS: token-frame drops
                        # are where scale-down churn could lose tokens
                        "DYN_CHAOS": "stream.send:drop=0.02",
                        "DYN_CHAOS_SEED": "1234",
                        "DYN_DRAIN_TIMEOUT": "8",
                        "DYN_LOG": "warning"}}}},
        }, f)

    rt = await DistributedRuntime.create()
    manager = ModelManager()
    watcher = service = operator = aggregator = runner = None
    results, peak = [], 1
    try:
        watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
        service = HttpService(manager, port=0, runtime=rt)
        await service.start()
        operator = await ProcessOperator(
            spec, plane=rt.plane, tick_s=0.25, drain_timeout=10.0).start()
        aggregator = await MetricsAggregator(rt.plane,
                                             stale_after_s=3.0).start()
        url = f"http://127.0.0.1:{service.port}"
        fuser = ObservationFuser(PrometheusMetricsSource(url), aggregator)
        # one decode-role service serves prefill+decode: pin the prefill
        # dimension, or its replica math eats the shared cooldown windows
        planner = make_planner(
            slo, PerfInterpolator([(1.0, 200.0), (2.0, 700.0),
                                   (4.0, 2500.0)]),
            PerfInterpolator([(24.0, 10.0), (48.0, 40.0), (96.0, 300.0)]),
            min_prefill_replicas=1, max_prefill_replicas=1)

        async def readiness():
            return await plane_readiness(rt.plane, "dynamo")

        ctl = AutoscaleController(
            slo, planner, fuser, VirtualConnector(rt.plane),
            readiness=readiness, metrics=rt.metrics, plane=rt.plane)
        runner = await AutoscaleRunner(ctl).start()
        for _ in range(300):  # first worker registered + model discovered
            if manager.list_models():
                break
            await asyncio.sleep(0.1)
        assert manager.list_models(), "mocker fleet never appeared"

        mix = Mix("interactive=0.5,standard=0.2,batch=0.3")
        rng, prng = np.random.default_rng(7), random.Random(7)
        inflight: set = set()
        t0 = time.monotonic()
        # after the cycle a trough trickle runs while the loop steps the
        # fleet back down, one cooldown window a step
        tail = 3 * slo.cooldown_down_s + 12.0
        async with aiohttp.ClientSession() as session:
            while (now := time.monotonic() - t0) < duration_s + tail:
                if now < duration_s:  # trough -> peak at period/2 -> down
                    rate = max(0.05, 2.2 + 1.8 * math.sin(
                        2 * math.pi * now / period - math.pi / 2))
                elif (ctl.applied.decode_replicas == slo.min_replicas
                      and operator._status()["services"]["decode"]["ready"]
                      == slo.min_replicas):
                    break  # fleet settled at the floor
                else:
                    rate = 0.4
                cls = mix.pick(prng)
                task = asyncio.get_running_loop().create_task(stream_request(
                    session, url, model, make_prompt(prng, 48), osl,
                    headers=qos_headers(None, cls)))
                inflight.add(task)
                task.add_done_callback(
                    lambda t, cls=cls: (inflight.discard(t),
                                        results.append((cls, t.result()))))
                peak = max(peak, ctl.applied.decode_replicas)
                await asyncio.sleep(float(rng.exponential(1.0 / rate)))
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)
        queue_depth = (await fuser()).queue_depth
        ready = operator._status()["services"]["decode"]["ready"]
    finally:
        if runner is not None:
            await runner.stop()
        if aggregator is not None:
            await aggregator.stop()
        if operator is not None:
            await operator.stop()  # drains the fleet
        if service is not None:
            await service.stop()
        if watcher is not None:
            await watcher.stop()
        await rt.shutdown()
        await server.stop()

    assert results and all(r.ok for _, r in results), [
        r.error for _, r in results if not r.ok]
    assert all(r.completion_tokens == osl for _, r in results)
    assert any(cls == "batch" for cls, _ in results)
    assert ctl.scale_ups >= 1 and ctl.scale_downs >= 1
    assert peak >= 2 and ready == slo.min_replicas
    assert queue_depth == 0
