"""Fleet flight recorder (docs/observability.md "Flight recorder"):
ring-bounded memory, inline anomaly tagging, fleet fan-out with dead-worker
drop, JSONL round-trip, mocker parity, trace head-sampling, hub event
instrumentation, engine compile visibility, tier occupancy gauges, and the
engine loop's phase clock (records' ``period_ms`` / ``phases``, the
``dynamo.<phase>`` trace annotations)."""

import asyncio
import json
import types

import msgpack
import pytest

from dynamo_tpu.observability import (
    FlightRecorder,
    StepRecord,
    Tracer,
    fetch_fleet_steps,
    serve_flight,
    trace_sampled,
)
from dynamo_tpu.observability import flight as flight_mod
from dynamo_tpu.observability.flight import (
    FLIGHT_PREFIX,
    PHASES,
    PhaseClock,
    TAG_COMPILE_STEADY,
    TAG_EMPTY,
    TAG_PREEMPT_STORM,
    TAG_SLOW,
    TAG_STARVED,
    register_recorder,
    unregister_recorder,
)
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.anyio


def make_recorder(**kw) -> FlightRecorder:
    kw.setdefault("service", "test")
    kw.setdefault("enabled", True)
    return FlightRecorder(**kw)


# ------------------------------------------------------------- ring + tags


def test_ring_bounded_under_10k_steps():
    rec = make_recorder(capacity=512)
    for i in range(10_000):
        rec.record("ragged", 2.0, decode_rows=4, chunk_tokens=8,
                   kv_tiers={"g1": i % 7})
    assert len(rec) == 512
    snap = rec.snapshot()
    assert len(snap) == 512
    # the ring keeps the NEWEST records and the seq keeps counting
    assert snap[-1]["seq"] == 10_000
    assert snap[0]["seq"] == 10_000 - 512 + 1
    assert rec.summary()["steps_total"] == 10_000
    # baseline/storm windows are bounded too (no unbounded growth)
    assert all(len(b[0]) <= 256 for b in rec._base.values())
    assert len(rec._storm) <= 32


def test_disabled_recorder_records_nothing():
    rec = make_recorder(enabled=False)
    assert rec.record("ragged", 1.0) is None
    assert len(rec) == 0


def test_slow_step_tag_needs_baseline_and_sigma():
    rec = make_recorder()
    for _ in range(40):
        r = rec.record("ragged", 5.0, decode_rows=1)
        assert TAG_SLOW not in r.tags  # steady baseline: no false tags
    slow = rec.record("ragged", 120.0, decode_rows=1)
    assert TAG_SLOW in slow.tags
    # the outlier joined the baseline AFTER tagging, not before
    assert rec.anomaly_counts[TAG_SLOW] == 1
    # too few samples → never tags (σ of 3 samples is noise)
    fresh = make_recorder()
    fresh.record("ragged", 1.0)
    r = fresh.record("ragged", 500.0)
    assert TAG_SLOW not in r.tags


def test_slow_step_baseline_is_per_kind():
    """A routine 30 ms prefill after a stretch of ~1 ms pipelined decode
    steps is NOT slow — a pooled baseline would tag every burst boundary."""
    rec = make_recorder()
    for _ in range(40):
        rec.record("decode_pipe", 1.0, decode_rows=4)
    r = rec.record("ragged", 30.0, prefill_chunks=1, chunk_tokens=64)
    assert TAG_SLOW not in r.tags  # no ragged baseline yet
    for _ in range(20):
        rec.record("ragged", 30.0, prefill_chunks=1, chunk_tokens=64)
    ok = rec.record("ragged", 30.2, prefill_chunks=1, chunk_tokens=64)
    assert TAG_SLOW not in ok.tags  # within the 0.5 ms jitter floor
    slow = rec.record("ragged", 400.0, prefill_chunks=1, chunk_tokens=64)
    assert TAG_SLOW in slow.tags
    # the decode baseline still catches ITS OWN outliers
    slow_d = rec.record("decode_pipe", 50.0, decode_rows=4)
    assert TAG_SLOW in slow_d.tags


def test_compile_steady_tag_and_warmup_grace():
    rec = make_recorder()
    rec.steady_after = 10
    early = rec.record("ragged", 50.0, compile_s=0.5, compile_sig="ragged:64")
    assert "compile" in early.tags and TAG_COMPILE_STEADY not in early.tags
    for _ in range(12):
        rec.record("ragged", 2.0)
    late = rec.record("ragged", 50.0, compile_s=0.5, compile_sig="ragged:8")
    assert TAG_COMPILE_STEADY in late.tags


def test_preempt_storm_tag_rolling_window():
    rec = make_recorder()
    rec.storm_threshold = 4
    # sparse preemptions never tag
    for i in range(60):
        r = rec.record("ragged", 2.0,
                       preempt_recompute=1 if i % 40 == 0 else 0)
        assert TAG_PREEMPT_STORM not in r.tags
    # a burst inside the window does; preempt-free records in between
    # do NOT get the tag (the tag marks steps that preempted)
    tagged = []
    for i in range(6):
        r = rec.record("ragged", 2.0, preempt_swap=1)
        tagged.append(TAG_PREEMPT_STORM in r.tags)
    assert any(tagged)
    calm = rec.record("ragged", 2.0)
    assert TAG_PREEMPT_STORM not in calm.tags


def test_starved_and_empty_tags():
    rec = make_recorder()
    r = rec.record("ragged", 2.0, decode_rows=3, starved_decode=2)
    assert TAG_STARVED in r.tags
    e = rec.record("empty", 50.0, waiting=4)
    assert TAG_EMPTY in e.tags
    # empty bubbles stay out of the slow-step baselines
    assert "empty" not in rec._base
    assert sum(len(b[0]) for b in rec._base.values()) == 1


def test_summary_math():
    rec = make_recorder()
    for i in range(10):
        rec.record("ragged", float(i + 1), decode_rows=2, chunk_tokens=3,
                   waiting=1, running=2, kv_tiers={"g1": 5, "g2": 1})
    s = rec.summary()
    assert s["steps_total"] == 10
    assert s["tokens_in_ring"] == 50
    # the shared interpolated estimator (observability/stats.quantile):
    # p50 of 1..10 interpolates between the 5th and 6th order statistics
    assert s["wall_p50_ms"] == 5.5
    assert s["wall_p95_ms"] == 9.55
    assert s["kv_tiers"] == {"g1": 5, "g2": 1}
    assert s["waiting"] == 1 and s["running"] == 2


# ------------------------------------------------------------ JSONL export


def test_jsonl_export_round_trips(tmp_path):
    rec = make_recorder()
    rec.record("ragged", 3.25, decode_rows=2, prefill_chunks=1,
               chunk_tokens=7, padded_tokens=4, compile_s=0.5,
               compile_sig="ragged:64", preempt_swap=1, starved_decode=1,
               kv_tiers={"g1": 3, "g4": 2}, qos_mix={"interactive": 2})
    rec.record("empty", 12.0, waiting=3)
    path = tmp_path / "steps.jsonl"
    n = rec.export_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert n == len(lines) == 2
    back = StepRecord.from_dict(lines[0])
    assert back.kind == "ragged" and back.wall_ms == 3.25
    assert back.decode_rows == 2 and back.chunk_tokens == 7
    assert back.compile_sig == "ragged:64" and back.preempt_swap == 1
    assert back.kv_tiers == {"g1": 3, "g4": 2}
    assert back.qos_mix == {"interactive": 2}
    assert "compile" in back.tags
    assert StepRecord.from_dict(lines[1]).kind == "empty"


def test_streaming_jsonl_env(tmp_path, monkeypatch):
    path = tmp_path / "live.jsonl"
    monkeypatch.setenv("DYN_STEP_JSONL", str(path))
    rec = make_recorder()
    rec.record("ragged", 1.0, decode_rows=1)
    rec.record("ragged", 2.0, decode_rows=1)
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [d["seq"] for d in lines] == [1, 2]


# ----------------------------------------------------------- fleet fan-out


async def test_fleet_fanout_merges_and_drops_dead_worker():
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create()
    rec = make_recorder(service="workerA")
    for _ in range(20):
        rec.record("mock", 2.0, decode_rows=1, kv_tiers={"g1": 4})
    name = register_recorder("workerA", rec)
    try:
        handle = await serve_flight(rt)
        # a dead worker: discovery key present, nothing serving its subject
        await rt.plane.kv_put(
            FLIGHT_PREFIX + "deadbeef",
            msgpack.packb({"subject": "flight-gone", "service": "dead"}))
        out = await fetch_fleet_steps(rt.plane, n=5, timeout=0.3)
        assert len(out) == 1  # dead worker dropped, live one served
        key = next(iter(out))
        assert key.endswith("/workerA")
        assert out[key]["summary"]["steps_total"] == 20
        assert len(out[key]["steps"]) == 5
        # summary-only query ships no step payloads
        out0 = await fetch_fleet_steps(rt.plane, n=0, timeout=0.3)
        assert "steps" not in out0[key]
        await handle.stop()
        assert await fetch_fleet_steps(rt.plane, timeout=0.3) == {}
    finally:
        unregister_recorder(name)
        await rt.shutdown()


async def test_frontend_fleet_steps_route():
    """GET /v1/fleet/steps serves the fan-out through the HTTP frontend."""
    import aiohttp

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create()
    rec = make_recorder(service="w0")
    rec.record("mock", 1.0, decode_rows=1)
    name = register_recorder("w0", rec)
    svc = HttpService(ModelManager(), host="127.0.0.1", port=0, runtime=rt)
    try:
        handle = await serve_flight(rt)
        port = await svc.start()
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{port}/v1/fleet/steps?n=3") as resp:
                assert resp.status == 200
                body = await resp.json()
        assert body["count"] == 1
        entry = next(iter(body["workers"].values()))
        assert entry["summary"]["steps_total"] == 1
        assert len(entry["steps"]) == 1
        await handle.stop()
    finally:
        unregister_recorder(name)
        await svc.stop()
        await rt.shutdown()


# ---------------------------------------------------------- mocker parity


async def test_mocker_flight_parity():
    """The mocker's simulated steps append the same record shape the real
    engine does (fleet tests see one timeline model)."""
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineArgs
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    eng = await MockEngine(MockEngineArgs(
        num_gpu_blocks=128, block_size=4, max_num_seqs=4,
        max_num_batched_tokens=64, speedup_ratio=100.0)).start()
    try:
        req = PreprocessedRequest(
            model="m", token_ids=list(range(1, 30)),
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(), eos_token_ids=[2])
        ctx = Context()
        n = 0
        async for out in eng.generate(req, ctx):
            n += len(out.get("token_ids") or [])
            if out.get("finish_reason"):
                break
        assert n >= 8
        snap = eng.flight.snapshot()
        assert snap, "mocker recorded no flight steps"
        kinds = {d["kind"] for d in snap}
        assert "mock" in kinds
        steps = [d for d in snap if d["kind"] == "mock"]
        assert any(d["chunk_tokens"] > 0 for d in steps)  # prefill visible
        assert any(d["decode_rows"] > 0 for d in steps)   # decode visible
        assert all("kv_tiers" in d for d in steps)
        s = eng.flight.summary()
        assert s["steps_total"] == len(snap)
    finally:
        await eng.stop()


# --------------------------------------------------------- trace sampling


def test_trace_sampling_deterministic_and_gating(monkeypatch):
    ids = [f"req-{i}" for i in range(400)]
    monkeypatch.setenv("DYN_TRACE_SAMPLE", "0.5")
    first = [trace_sampled(i) for i in ids]
    assert first == [trace_sampled(i) for i in ids]  # deterministic
    assert 0.3 < sum(first) / len(first) < 0.7
    # rate 0: every span degrades to the noop (bounded overhead)
    monkeypatch.setenv("DYN_TRACE_SAMPLE", "0")
    tracer = Tracer(service="t", capacity=8)
    ctx = Context()
    with tracer.span("http.request", ctx) as sp:
        sp.set(a=1)
    assert tracer.all_spans() == []
    assert tracer.record_hop(ctx, ctx.child_traceparent()).span_id == ""
    # rate 1 (and unset): everything records
    monkeypatch.setenv("DYN_TRACE_SAMPLE", "1.0")
    with tracer.span("http.request", ctx):
        pass
    assert len(tracer.all_spans()) == 1
    # malformed rate falls back to record-everything, not crash
    monkeypatch.setenv("DYN_TRACE_SAMPLE", "bogus")
    with tracer.span("http.request", ctx):
        pass
    assert len(tracer.all_spans()) == 2


async def test_unsampled_trace_http_response(monkeypatch):
    """/v1/traces/{id} says "not sampled" instead of 404 when the id was
    head-sampled out (the operator must be able to tell the difference)."""
    import aiohttp

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager

    # find an id the 0.001-rate sampler drops (virtually all of them)
    monkeypatch.setenv("DYN_TRACE_SAMPLE", "0.001")
    rid = next(f"r-{i}" for i in range(1000)
               if not trace_sampled(f"r-{i}", 0.001))
    svc = HttpService(ModelManager(), host="127.0.0.1", port=0)
    try:
        port = await svc.start()
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{port}/v1/traces/{rid}") as resp:
                assert resp.status == 200
                body = await resp.json()
        assert body["sampled"] is False
        assert "DYN_TRACE_SAMPLE" in body["reason"]
        # a SAMPLED id with no spans still 404s (trace expired ≠ unsampled)
        hit = next(f"r-{i}" for i in range(1000)
                   if trace_sampled(f"r-{i}", 0.001))
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{port}/v1/traces/{hit}") as resp:
                assert resp.status == 404
    finally:
        await svc.stop()


# ------------------------------------------------------------- hub metrics


async def test_hub_event_counters_and_publish_latency():
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    await plane.kv_put("k1", b"v")
    await plane.kv_delete("k1")
    await plane.publish("subj", b"x")
    await plane.stream_publish("st", b"y")
    await plane.queue_push("q", b"z")
    stats = await plane.hub_stats()
    ev = stats["events"]
    assert ev["kv_put"] == 1 and ev["kv_delete"] == 1
    assert ev["publish"] == 1 and ev["stream_publish"] == 1
    assert ev["queue_push"] == 1
    pub = stats["publish_seconds"]
    assert pub["count"] == 2 and pub["sum"] > 0
    assert pub["buckets"]["+Inf"] == 2
    await plane.close()


async def test_hub_stats_over_tcp_and_metrics_render():
    from dynamo_tpu.metrics.main import MetricsService
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.control_plane import (
        ControlPlaneServer, RemoteControlPlane,
    )

    server = ControlPlaneServer("127.0.0.1", 0)
    addr = await server.start()
    plane = await RemoteControlPlane(addr).connect()
    try:
        await plane.publish("some.subject", b"p")
        stats = await plane.hub_stats()
        assert stats["events"]["publish"] == 1
        rt = await DistributedRuntime.create(plane=plane, owns_plane=False)
        svc = MetricsService(rt)
        text = svc.render(prefill_queue_depth=0, hub=stats)
        assert '# TYPE dynamo_hub_events_total counter' in text
        assert 'dynamo_hub_events_total{kind="publish"} 1' in text
        assert "# TYPE dynamo_hub_publish_seconds histogram" in text
        assert "dynamo_hub_publish_seconds_count 1" in text
        await rt.shutdown()
    finally:
        await plane.close()
        await server.stop()


# ------------------------------------------------------------- phase clock


def test_phase_clock_laps_sum_to_the_period(monkeypatch):
    """A lap clock: entering a phase ends the one before, a phase entered
    twice accumulates, a worker's stamp splits a wait into ``device_wait``
    and ``lag``, and ``cut`` hands over the laps and starts again. (Times
    are binary fractions, so the sums are exact.)"""
    now = [10.0]
    monkeypatch.setattr(flight_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0]))
    clock = PhaseClock()

    def after(seconds, phase, **kw):
        now[0] += seconds
        clock.mark(phase, **kw)

    after(0.25, "plan")          # 0.25 s in no phase: ``other``
    after(0.5, "build")
    after(1.0, "dispatch")
    after(0.125, "build")        # a second build accumulates
    after(2.0, "device_wait")
    # the result landed 0.5 s into a wait the loop left after 0.75 s
    after(0.75, "lag", at=now[0] + 0.5)
    clock.mark("commit")
    now[0] += 0.0625
    period, phases = clock.cut()
    assert phases == {"other": 250.0, "plan": 500.0, "build": 3000.0,
                      "dispatch": 125.0, "device_wait": 500.0, "lag": 250.0,
                      "commit": 62.5}
    assert period == sum(phases.values()) == 4687.5
    # the cut reset the laps, the open phase goes on, zeros are left out
    now[0] += 0.5
    assert clock.cut() == (500.0, {"commit": 500.0})
    # a stamp from before the wait began (the result was there already):
    # no ``device_wait`` at all, the whole await is ``lag``
    clock.mark("device_wait")
    after(0.25, "lag", at=now[0] - 5.0)
    clock.mark("commit")
    assert clock.cut() == (250.0, {"lag": 250.0})
    assert set(phases) <= set(PHASES)


def test_step_record_carries_period_and_sparse_phases():
    rec = make_recorder()
    r = rec.record("decode_pipe", 19.0, period_ms=9.5,
                   phases={"build": 3.0, "dispatch": 2.25, "commit": 4.25},
                   dispatch_ms=2.25, decode_rows=8)
    d = r.to_dict()
    assert d["period_ms"] == 9.5 and d["dispatch_ms"] == 2.25
    assert d["phases"] == {"build": 3.0, "dispatch": 2.25, "commit": 4.25}
    back = StepRecord.from_dict(json.loads(json.dumps(d)))
    assert back.period_ms == 9.5 and back.phases == d["phases"]
    assert back.wall_ms == 19.0   # the pipe latency keeps its own field
    # a recorder that runs no clock (the mocker) keeps both off the wire
    bare = rec.record("mock", 5.0).to_dict()
    assert "period_ms" not in bare and "phases" not in bare
    assert StepRecord.from_dict(bare).phases == {}


# ------------------------------------------- engine parity + compile + tiers


@pytest.fixture(scope="module")
def tiny_engine_cfg():
    from dynamo_tpu.engine.config import EngineArgs, ModelConfig

    return ModelConfig.tiny(), dict(
        block_size=4, num_blocks=64, max_num_seqs=4,
        max_num_batched_tokens=64, max_model_len=256,
        enable_prefix_caching=False)


async def test_engine_flight_records_and_compile_visibility(tiny_engine_cfg):
    """A real (tiny-cpu) engine step appends tagged records, counts its
    post-warmup jit traces, and reports tier occupancy."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    cfg, base = tiny_engine_cfg
    eng = AsyncJaxEngine(cfg, EngineArgs(**base))
    try:
        req = PreprocessedRequest(
            model="m", token_ids=list(range(1, 30)),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        n = 0
        async for out in eng.generate(req):
            n += len(out.token_ids)
        assert n == 6
        snap = eng.flight.snapshot()
        assert snap
        first = snap[0]
        assert first["kind"] == "ragged" and first["chunk_tokens"] == 29
        assert "compile" in first["tags"]  # cold engine: first trace
        assert first["compile_s"] > 0 and first["compile_sig"]
        assert first["dispatch_ms"] > 0
        assert set(first["kv_tiers"]) == {"g1", "g2", "g3", "g4"}
        # compile accounting: the dispatch kinds this run traced
        assert eng.compile_events.get("ragged") == 1
        assert eng.compile_seconds["ragged"] > 0
        # tier occupancy: g1 empty again after the stream finished
        occ = eng.kv_tier_occupancy()
        assert occ["g1"]["blocks"] == 0
        assert occ["g2"] == {"blocks": 0, "bytes": 0}
    finally:
        await eng.close()


async def test_flight_wide_tile_rows_counts_rows_above_the_small_tile(
        tiny_engine_cfg):
    """``wide_tile_rows`` of a step's record is the number of its rows with
    more than 8 query tokens (the ragged kernel's wide query tile), counted
    where the step's rows3 is built; decode steps carry none."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    cfg, base = tiny_engine_cfg
    eng = AsyncJaxEngine(cfg, EngineArgs(**base))
    seen = []
    count = eng._count_wide_rows

    def spy(rows3):
        seen.append(int((rows3[..., 1] > 8).sum()))
        count(rows3)

    eng._count_wide_rows = spy
    try:
        for n_prompt in (29, 8, 9, 100):  # 100 = chunks of 64 + 36 tokens
            req = PreprocessedRequest(
                model="m", token_ids=list(range(1, n_prompt + 1)),
                stop_conditions=StopConditions(max_tokens=3, ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            async for _ in eng.generate(req):
                pass
        snap = eng.flight.snapshot()
        per_step = [r.get("wide_tile_rows", 0) for r in snap
                    if r["kind"] == "ragged"]
        assert per_step == seen and sum(seen) == 4, (per_step, seen)
        assert eng.wide_tile_rows_total == 4
        assert all(r.get("wide_tile_rows", 0) <= r["prefill_chunks"]
                   for r in snap)
    finally:
        await eng.close()


async def test_engine_flight_disabled_is_pure_observation(tiny_engine_cfg):
    """DYN_FLIGHT=0 arm: identical token stream, zero records (recording
    is pure observation)."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    cfg, base = tiny_engine_cfg

    async def run(flight_on: bool) -> list:
        eng = AsyncJaxEngine(cfg, EngineArgs(**base))
        eng.flight.enabled = flight_on
        req = PreprocessedRequest(
            model="m", token_ids=list(range(1, 20)),
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        recs, clock = len(eng.flight), eng._clock
        await eng.close()
        return toks, recs, clock

    on_toks, on_recs, on_clock = await run(True)
    off_toks, off_recs, off_clock = await run(False)
    assert on_toks == off_toks
    assert on_recs > 0 and off_recs == 0
    # nothing to feed: the phase clock is not even made, a mark is one test
    assert on_clock is not None and off_clock is None


@pytest.mark.parametrize("profiled", [False, True],
                         ids=["tagged", "profiled"])
async def test_engine_storm_and_steady_compile(profiled, monkeypatch,
                                               tmp_path):
    """A SEEDED preempt storm on a real engine — batch-class streams fill
    every slot, then an interactive burst lands and QoS admission
    preemption evicts a batch victim per arrival (recompute mode, so each
    eviction is a genuine preemption) — is tagged ``preempt-storm``; then
    prompts sized to ragged token buckets the storm never dispatched trace
    fresh signatures in steady state and are tagged ``compile-steady``.
    With DYN_PROFILE_ON_ANOMALY set, the anomalies arm at least one REAL
    ``jax.profiler`` capture, capped by the max-captures budget, with the
    artifact on disk and its path on the triggering record."""
    import glob

    import numpy as np

    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    if profiled:
        monkeypatch.setenv("DYN_PROFILE_ON_ANOMALY", str(tmp_path))
        monkeypatch.setenv("DYN_PROFILE_MAX_CAPTURES", "2")
        monkeypatch.setenv("DYN_PROFILE_COOLDOWN_S", "0")
        monkeypatch.setenv("DYN_PROFILE_STEPS", "4")
    cfg = ModelConfig.tiny()
    slots, budget = 6, 256
    rng = np.random.default_rng(31)

    async def one(eng, n_prompt, osl, cls):
        ctx = Context()
        ctx.priority = cls
        r = PreprocessedRequest(
            model="m",
            token_ids=rng.integers(1, cfg.vocab_size, n_prompt).tolist(),
            stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        n = 0
        async for out in eng.generate(r, ctx):
            n += len(out.token_ids)
        assert n == osl

    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=256, max_num_seqs=slots,
        max_num_batched_tokens=budget, max_model_len=2 * budget,
        enable_prefix_caching=False, preempt_swap=False))
    try:
        eng.flight.steady_after = 16  # tiny workload: steady state is near
        batch = [asyncio.ensure_future(one(eng, 24, 48, "batch"))
                 for _ in range(slots)]
        for _ in range(20000):  # every slot decoding before the burst lands
            if sum(s.generated > 0 for s in eng.scheduler.running) >= slots:
                break
            await asyncio.sleep(0.001)
        inter = [asyncio.ensure_future(one(eng, 12, 8, "interactive"))
                 for _ in range(5)]
        await asyncio.gather(*batch, *inter)
        assert eng.scheduler.preempt_recompute_total > 0
        # sent alone, a prompt's one chunk IS the packed total, so each
        # traces a fresh (ragged, T) signature mid-traffic
        unseen = [b for b in eng.args.ragged_token_buckets
                  if ("ragged", b) not in eng.compiled_signatures
                  and b <= budget][:4]
        assert unseen
        for b in unseen:
            await one(eng, b, 2, "standard")
        anoms = dict(eng.flight.summary()["anomalies"])
        assert anoms.get("preempt-storm"), anoms
        assert anoms.get("compile-steady"), anoms
        assert eng.compile_events.get("ragged", 0) >= len(unseen)
        prof = eng.anomaly_profiler
        if not profiled:
            assert prof is None
        else:
            assert 1 <= prof.captures <= 2
            assert glob.glob(str(tmp_path / "**" / "*.pb"), recursive=True)
            assert any(r.get("profile_path")
                       for r in eng.flight.snapshot())
    finally:
        await eng.close()


def _tiny_request(n_prompt, max_tokens):
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    return PreprocessedRequest(
        model="m", token_ids=list(range(1, n_prompt + 1)),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


async def _stream(eng, n_prompt, max_tokens):
    n = 0
    async for out in eng.generate(_tiny_request(n_prompt, max_tokens)):
        n += len(out.token_ids)
    assert n == max_tokens


async def test_engine_phase_clock_through_mixed_and_pipelined_steps(
        tiny_engine_cfg):
    """Every step record says what its step cost the loop (``period_ms``)
    and what the loop did meanwhile (``phases``, summing to it): a pause
    between two requests is the NEXT record's ``idle`` and nobody else's, a
    pipelined step's dispatch is timed like a mixed step's, and a plan that
    could run nothing leaves a ``blocked`` record with no step phase."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.scheduler import StepPlan

    cfg, base = tiny_engine_cfg
    eng = AsyncJaxEngine(cfg, EngineArgs(**base))
    try:
        await _stream(eng, 29, 6)
        await asyncio.sleep(0.05)           # the loop has nothing to run
        before = len(eng.flight.snapshot())
        # the next plan finds nothing it can run, once: the loop waits for
        # a wake-up and leaves an ``empty`` record
        plan, calls = eng.scheduler.plan, []

        def plan_once_empty():
            calls.append(1)
            return StepPlan() if len(calls) == 1 else plan()

        eng.scheduler.plan = plan_once_empty
        # 100 tokens = chunks of 64 + 36 beside the other's decode rows
        await asyncio.gather(_stream(eng, 20, 5), _stream(eng, 100, 4))
        snap = eng.flight.snapshot()
    finally:
        await eng.close()
    steps = [r for r in snap if r["kind"] in ("ragged", "decode_pipe")]
    assert {r["kind"] for r in steps} == {"ragged", "decode_pipe"}
    for r in snap:
        assert set(r["phases"]) <= set(PHASES), r
        assert abs(sum(r["phases"].values()) - r["period_ms"]) <= 0.01, r
        assert r.get("dispatch_ms", 0.0) == r["phases"].get("dispatch", 0.0)
    for r in steps:
        assert {"commit", "record"} <= set(r["phases"]), r
        if r["kind"] == "ragged":
            assert ({"build", "put", "dispatch", "device_wait"}
                    <= set(r["phases"])), r
            # its sampler runs in the worker thread: ``device_wait``
            assert "sample" not in r["phases"], r
    # the depth-2 pipe dispatches step N+1 before it commits N: a run's
    # first record holds two builds and dispatches, the one that drains the
    # pipe none, every one between exactly one
    piped = [r for r in steps if r["kind"] == "decode_pipe"]
    drained = [r for r in piped if "build" not in r["phases"]]
    assert all(not {"put", "dispatch", "sample"} & set(r["phases"])
               for r in drained)
    for r in piped:
        if r not in drained:
            assert r["dispatch_ms"] > 0, r
            assert {"put", "dispatch", "sample"} <= set(r["phases"]), r
    assert len(piped) > len(drained)
    assert len(drained) <= sum("plan" in r["phases"] for r in piped)
    # the pause: in the first record after it and nowhere else
    assert [i for i, r in enumerate(snap) if "idle" in r["phases"]] == [before]
    # the plan that could run nothing
    empty = [r for r in snap if r["kind"] == "empty"]
    assert len(empty) == 1 and snap.index(empty[0]) == before
    assert {"plan", "blocked"} <= set(empty[0]["phases"])
    assert not ({"build", "put", "dispatch", "sample", "device_wait"}
                & set(empty[0]["phases"]))
    assert all("blocked" not in r["phases"] for r in steps)


@pytest.mark.parametrize("gate", ["", "1"], ids=["gate-off", "gate-on"])
async def test_phase_annotations_are_flat_and_gated(gate, monkeypatch,
                                                    tiny_engine_cfg):
    """Under DYN_JAX_PROFILER=1 the clock's transitions open and close
    ``dynamo.<phase>`` trace annotations, one at a time (a device trace
    tags an idle gap by the annotation that holds it: nested ones would
    hide); with the gate off none is made."""
    import jax.profiler

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.observability import profiler

    events = []

    class Recorded:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))

        def __exit__(self, *exc):
            events.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    monkeypatch.setenv("DYN_JAX_PROFILER", gate)
    profiler._reset_for_tests()
    cfg, base = tiny_engine_cfg
    eng = AsyncJaxEngine(cfg, EngineArgs(**base))
    try:
        await asyncio.gather(_stream(eng, 20, 5), _stream(eng, 100, 4))
        recorded = len(eng.flight)
    finally:
        await eng.close()
        profiler._reset_for_tests()
    assert recorded > 0               # the flight records need no gate
    if not gate:
        assert events == []
        return
    names = {name for _, name in events}
    assert names <= {"dynamo." + p for p in PHASES}
    assert {"dynamo.build", "dynamo.put", "dynamo.dispatch",
            "dynamo.sample", "dynamo.device_wait", "dynamo.commit",
            "dynamo.record"} <= names
    # open, close, open, close ... each closed before the next opens, the
    # last one by the engine's close()
    assert len(events) % 2 == 0
    for (what0, name0), (what1, name1) in zip(events[::2], events[1::2]):
        assert (what0, what1) == ("open", "close") and name0 == name1
