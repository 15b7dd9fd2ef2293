"""Aux subsystems: canary health checks, recorders, metrics aggregation."""

import asyncio
import json

import pytest

from dynamo_tpu.llm.recorder import Recorder, KvRecorder, load_events, replay
from dynamo_tpu.runtime.health_check import HealthCheckConfig, HealthCheckManager

pytestmark = pytest.mark.anyio


class FakeClient:
    """Minimal Client surface for the health manager."""

    def __init__(self, healthy: set, all_ids):
        self.healthy = healthy
        self.ids = list(all_ids)
        self._down = set()

    def instance_ids(self):
        return list(self.ids)

    def report_instance_down(self, iid):
        self._down.add(iid)

    def report_instance_up(self, iid):
        self._down.discard(iid)

    async def generate(self, payload, mode="direct", instance_id=None):
        if instance_id not in self.healthy:
            raise RuntimeError("no responders")

        async def stream():
            yield {"ok": True}
        return stream()


async def test_health_check_marks_down_and_restores():
    client = FakeClient(healthy={1}, all_ids=[1, 2])
    cfg = HealthCheckConfig(check_interval_s=0.05, timeout_s=0.5,
                            failure_threshold=2)
    mgr = await HealthCheckManager(client, cfg).start()
    for _ in range(100):
        if 2 in client._down:
            break
        await asyncio.sleep(0.02)
    assert 2 in client._down and 1 not in client._down

    client.healthy.add(2)  # instance recovers → canary restores routing
    for _ in range(100):
        if 2 not in client._down:
            break
        await asyncio.sleep(0.02)
    assert 2 not in client._down
    await mgr.stop()


async def test_health_check_hung_stream_counts_as_failure():
    """A worker that accepts the canary but never yields must be marked down
    (timeout covers connect + first frame, not just obtaining the stream)."""

    class HangClient(FakeClient):
        async def generate(self, payload, mode="direct", instance_id=None):
            async def stream():
                await asyncio.sleep(3600)
                yield {}
            return stream()

    client = HangClient(healthy={1}, all_ids=[1])
    cfg = HealthCheckConfig(check_interval_s=0.05, timeout_s=0.1,
                            failure_threshold=2)
    mgr = await HealthCheckManager(client, cfg).start()
    for _ in range(100):
        if 1 in client._down:
            break
        await asyncio.sleep(0.02)
    assert 1 in client._down
    await mgr.stop()


async def test_default_canary_is_valid_request():
    """The default canary must parse as a real PreprocessedRequest and be
    servable by a real engine handler (ADVICE r1: {"health_check": true}
    failed from_wire on every probe)."""
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineArgs
    from dynamo_tpu.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.health_check import default_canary_payload

    payload = default_canary_payload()
    req = PreprocessedRequest.from_wire(payload)  # must not raise
    assert req.stop_conditions.max_tokens == 1

    engine = await MockEngine(MockEngineArgs()).start()
    got = []
    async for out in engine.generate(payload, Context()):
        got.append(out)
    assert got, "canary produced no frames from a real handler"
    await engine.stop()


async def test_recorder_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    r = Recorder(path)
    r.record("request", {"prompt": "hi"})
    r.record("response", {"token_ids": [1, 2]})
    r.flush()
    evs = load_events(path)
    assert [e["kind"] for e in evs] == ["request", "response"]
    got = []
    async for ev in replay(path):
        got.append(ev["data"])
    assert got[0] == {"prompt": "hi"}


async def test_kv_recorder_captures_stream(tmp_path):
    import msgpack

    from dynamo_tpu.router.protocols import KvCacheEvent, RouterEvent, StoredBlock
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    path = str(tmp_path / "kv.jsonl")
    rec = await KvRecorder(plane, path).start()
    ev = RouterEvent(7, KvCacheEvent.stored(
        1, None, [StoredBlock(block_hash=11, tokens_hash=22)]))
    await plane.stream_publish("kv_events", msgpack.packb(ev.to_wire()))
    for _ in range(50):
        await asyncio.sleep(0.01)
        rec.recorder.flush()
        if load_events(path):
            break
    await rec.stop()
    evs = load_events(path)
    assert evs and evs[0]["data"]["worker_id"] == 7


@pytest.mark.anyio
async def test_run_batch_entrypoint(tmp_path):
    """``run.py in=batch``: JSONL in → JSONL out through the full pipeline
    (ref: entrypoint/input.rs:32 batch mode)."""
    import asyncio
    import json
    import os
    import sys

    inp = tmp_path / "reqs.jsonl"
    outp = tmp_path / "resp.jsonl"
    reqs = [
        {"messages": [{"role": "user", "content": "hello world"}],
         "max_tokens": 4},
        {"prompt": "the quick brown fox", "max_tokens": 3},
        {"messages": [{"role": "user", "content": "tell me about tokens"}],
         "max_tokens": 2},
    ]
    inp.write_text("".join(json.dumps(r) + "\n" for r in reqs))

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
               DYN_LOG="warning")
    env.pop("DYN_CONTROL_PLANE", None)  # in-process plane
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_tpu.run", "in=batch", "out=mocker",
        "--model", "mock", "--input-file", str(inp),
        "--output-file", str(outp),
        env=env, stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT)
    out, _ = await asyncio.wait_for(proc.communicate(), 120)
    assert proc.returncode == 0, out.decode()
    assert b"BATCH_DONE 3/3 ok" in out, out.decode()

    lines = [json.loads(line) for line in outp.read_text().splitlines()]
    assert len(lines) == 3
    assert lines[0]["object"] == "chat.completion"
    assert lines[0]["choices"][0]["finish_reason"] == "length"
    assert lines[1]["object"] == "text_completion"
    assert lines[1]["choices"][0]["finish_reason"] == "length"


async def test_system_status_server_and_config_wiring():
    """DYN_SYSTEM_PORT starts the /health /live /metrics server on the
    runtime (ref: system_status_server.rs); health-check knobs flow from
    RuntimeConfig into HealthCheckConfig.from_runtime."""
    import aiohttp

    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.health_check import HealthCheckConfig
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    rc = RuntimeConfig.load(env={"DYN_SYSTEM_PORT": "18977",
                                 "DYN_HEALTH_CHECK_INTERVAL": "7.5",
                                 "DYN_HEALTH_CHECK_FAILURES": "5"})
    hc = HealthCheckConfig.from_runtime(rc)
    assert hc.check_interval_s == 7.5 and hc.failure_threshold == 5

    rt = await DistributedRuntime.create(config=rc)
    try:
        rt.metrics.counter("aux_test_total", "test").inc(3)
        async with aiohttp.ClientSession() as s:
            async with s.get("http://127.0.0.1:18977/health") as r:
                assert (await r.json())["status"] == "ready"
            async with s.get("http://127.0.0.1:18977/live") as r:
                assert (await r.json())["live"] is True
            async with s.get("http://127.0.0.1:18977/metrics") as r:
                body = await r.text()
                assert "dynamo_aux_test_total 3" in body
                assert "dynamo_uptime_seconds" in body
    finally:
        await rt.shutdown()


async def test_tracker_child_after_join_is_closed():
    """A child created after join() must refuse spawns (structured
    concurrency cannot leak past the shutdown drain)."""
    import pytest as _pytest

    from dynamo_tpu.runtime.tasks import TaskTracker

    t = TaskTracker("root")
    ran = []

    async def work():
        ran.append(1)

    t.spawn(work())
    await t.join()
    late = t.child("late")

    async def never():
        ran.append(2)

    with _pytest.raises(RuntimeError):
        late.spawn(never())
    assert ran == [1]


def test_trace_replay_blocks_are_shared_and_deterministic():
    """Two trace records sharing hash_ids must expand to identical token
    prefixes (that's the whole prefix-caching signal), and expansion is
    stable across calls."""
    from benchmarks.trace_replay import block_tokens_for, prompt_for, synthesize

    assert block_tokens_for(42, 16) == block_tokens_for(42, 16)
    assert block_tokens_for(42, 16) != block_tokens_for(43, 16)

    a = {"timestamp": 0, "input_length": 140, "output_length": 8,
         "hash_ids": [7, 8]}
    b = {"timestamp": 999, "input_length": 150, "output_length": 8,
         "hash_ids": [7, 8, 9]}
    pa, pb = prompt_for(a, 64), prompt_for(b, 64)
    assert len(pa) == 140 and len(pb) == 150
    assert pa[:128] == pb[:128]          # shared 2-block prefix
    assert pa[128:] != pb[128:140]       # unique tails diverge

    tr = synthesize(50, block_tokens=32, seed=1)
    assert len(tr) == 50
    assert tr == synthesize(50, block_tokens=32, seed=1)  # reproducible
    ts = [r["timestamp"] for r in tr]
    assert ts == sorted(ts)
    # prefix sharing exists in the synthetic tree
    from collections import Counter
    first_blocks = Counter(tuple(r["hash_ids"][:1]) for r in tr)
    assert max(first_blocks.values()) > 1


def test_gauge_scrape_callbacks_with_labels():
    """Scrape-time gauge callbacks carry labeled samples (the engine's
    step-trace wiring in engine/main relies on this)."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    m = MetricsRegistry()
    g = m.gauge("engine_step_mean_ms", "x")
    state = {"decode": 12.5, "prefill": 230.0}
    g.add_callback(lambda: {(("kind", k),): v for k, v in state.items()})
    out = m.render()
    assert 'dynamo_engine_step_mean_ms{kind="decode"} 12.5' in out
    assert 'dynamo_engine_step_mean_ms{kind="prefill"} 230.0' in out
    state["decode"] = 99.0  # live: re-evaluated per scrape
    assert 'kind="decode"} 99.0' in m.render()


def test_settle_heap_sets_the_built_worker_aside_from_collection():
    """What lives as long as the worker (the step programs' jaxprs) is not
    walked by later full collections: they stalled every stream ~250 ms."""
    import gc

    from dynamo_tpu.engine.main import settle_heap

    from dynamo_tpu.engine import main as M

    built = [[i] for i in range(1000)]
    before = gc.get_threshold()
    try:
        assert settle_heap() == gc.get_freeze_count() >= 1001
        assert not any(o is built for o in gc.get_objects())
        later = [[0]]
        assert any(o is later for o in gc.get_objects())
        # young collections as before, the oldest generation rarely
        assert gc.get_threshold() == (*before[:2], M.FULL_COLLECTION_EVERY)
        settle_heap()
        assert gc.callbacks.count(M._note_collection) == 1
    finally:
        gc.unfreeze()
        gc.set_threshold(*before)
        gc.callbacks.remove(M._note_collection)
    assert any(o is built for o in gc.get_objects())


def test_a_collection_that_stops_the_worker_is_logged(caplog, monkeypatch):
    import types

    from dynamo_tpu.engine import main as M

    clock = iter([10.0, 10.005, 20.0, 20.120])
    monkeypatch.setattr(M, "time", types.SimpleNamespace(
        perf_counter=lambda: next(clock)))
    with caplog.at_level("WARNING", logger="dynamo.engine.main"):
        for _ in range(2):
            M._note_collection("start", {"generation": 2})
            M._note_collection("stop", {"generation": 2, "collected": 7})
    assert [r.getMessage() for r in caplog.records] == [
        "garbage collection of generation 2 stopped the worker for 120 ms "
        "(7 collected)"]
