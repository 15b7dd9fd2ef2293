"""Endpoint serve/client round trips: in-process and cross-runtime over TCP."""

import asyncio

import pytest

from dynamo_tpu.runtime import (
    Context,
    ControlPlaneServer,
    DistributedRuntime,
    NoRespondersError,
    RemoteControlPlane,
    StreamError,
)

pytestmark = pytest.mark.anyio


async def counting_handler(request, ctx: Context):
    n = request["n"]
    for i in range(n):
        yield {"i": i, "req": request.get("tag", "")}


@pytest.fixture
async def local_rt():
    rt = await DistributedRuntime.create(config=None)
    yield rt
    await rt.shutdown()


@pytest.fixture
async def cluster():
    """Two runtimes (worker, client) joined through a real TCP control plane."""
    server = ControlPlaneServer()
    addr = await server.start()
    worker_rt = await DistributedRuntime.create(
        plane=await RemoteControlPlane(addr).connect(), config=_cfg()
    )
    client_rt = await DistributedRuntime.create(
        plane=await RemoteControlPlane(addr).connect(), config=_cfg()
    )
    yield worker_rt, client_rt
    await worker_rt.shutdown()
    await client_rt.shutdown()
    await server.stop()


def _cfg():
    from dynamo_tpu.runtime.config import RuntimeConfig

    return RuntimeConfig(control_plane_address=None, lease_ttl=5.0, namespace="test")


async def test_inprocess_roundtrip(local_rt):
    ep = local_rt.namespace("ns").component("comp").endpoint("gen")
    handle = await ep.serve_endpoint(counting_handler)
    client = await ep.client().start()
    await client.wait_for_instances(timeout=5)

    stream = await client.generate({"n": 5, "tag": "x"})
    items = [item async for item in stream]
    assert items == [{"i": i, "req": "x"} for i in range(5)]
    await client.stop()
    await handle.stop()


async def test_cross_runtime_roundtrip(cluster):
    worker_rt, client_rt = cluster
    ep_w = worker_rt.namespace("ns").component("comp").endpoint("gen")
    handle = await ep_w.serve_endpoint(counting_handler)

    ep_c = client_rt.namespace("ns").component("comp").endpoint("gen")
    client = await ep_c.client().start()
    ids = await client.wait_for_instances(timeout=5)
    assert ids == [handle.lease_id]

    stream = await client.generate({"n": 100, "tag": "remote"})
    items = [item async for item in stream]
    assert len(items) == 100
    assert items[99] == {"i": 99, "req": "remote"}
    await client.stop()


async def test_no_responders(local_rt):
    ep = local_rt.namespace("ns").component("comp").endpoint("nothing")
    client = await ep.client().start()
    with pytest.raises(NoRespondersError):
        await client.generate({"n": 1})
    await client.stop()


async def test_handler_error_propagates(cluster):
    worker_rt, client_rt = cluster

    async def bad_handler(request, ctx):
        yield {"ok": 1}
        raise RuntimeError("boom")

    ep_w = worker_rt.namespace("ns").component("c").endpoint("bad")
    await ep_w.serve_endpoint(bad_handler)
    client = await client_rt.namespace("ns").component("c").endpoint("bad").client().start()
    await client.wait_for_instances(timeout=5)

    stream = await client.generate({})
    with pytest.raises(StreamError):
        async for _ in stream:
            pass
    await client.stop()


async def test_cancellation_stops_worker(cluster):
    worker_rt, client_rt = cluster
    produced = []

    async def slow_handler(request, ctx: Context):
        for i in range(1000):
            if ctx.cancelled:
                return
            produced.append(i)
            yield i
            await asyncio.sleep(0.01)

    ep_w = worker_rt.namespace("ns").component("c").endpoint("slow")
    await ep_w.serve_endpoint(slow_handler)
    client = await client_rt.namespace("ns").component("c").endpoint("slow").client().start()
    await client.wait_for_instances(timeout=5)

    ctx = Context()
    stream = await client.generate({}, ctx=ctx)
    got = []
    async for item in stream:
        got.append(item)
        if len(got) == 3:
            await stream.cancel()
            break
    await asyncio.sleep(0.5)
    assert len(produced) < 100  # worker actually stopped early
    await client.stop()


async def test_shutdown_with_silent_sender(local_rt):
    """A sender that connected, sent the prologue and then froze (neither
    data nor FIN) must not hold shutdown(): the runtime closes the
    connection itself and the receiver ends in the retryable stream error."""
    from dynamo_tpu.runtime.codec import read_frame, write_frame
    from dynamo_tpu.runtime.context import STREAM_ERR_MSG

    server = await local_rt.response_server()
    info, receiver = server.register_stream(Context())
    reader, writer = await asyncio.open_connection("127.0.0.1", info.port)
    try:
        await write_frame(writer, {"stream_id": info.stream_id})
        assert (await read_frame(reader))["t"] == "ok"
        # ... and now the peer says nothing more
        await asyncio.wait_for(local_rt.shutdown(), 2.0)
        with pytest.raises(StreamError) as ei:
            async for _ in receiver:
                pass
        assert str(ei.value) == STREAM_ERR_MSG and ei.value.retryable
    finally:
        writer.close()


async def test_spawned_handler_survives_gc_until_done():
    """The read loops drop the task they spawn per request. The event loop
    holds a task weakly, so one that waits on something nothing else
    references (an ack future) is a collectable cycle: it vanished mid-wait
    and its request sat out the 10 s timeout. ``_spawn_kept`` holds it until
    it is done, and not after."""
    import gc
    import weakref

    from dynamo_tpu.runtime import control_plane as cp

    waits, done = [], []

    async def handler():
        fut = asyncio.get_running_loop().create_future()
        waits.append(weakref.ref(fut))  # only the task itself holds fut
        await fut
        done.append(True)

    task = weakref.ref(cp._spawn_kept(handler()))
    await asyncio.sleep(0)
    gc.collect()
    assert task() is not None and task() in cp._kept_tasks
    assert waits[0]() is not None, "the waiting handler was collected"
    waits[0]().set_result(None)
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert done == [True]
    assert all(t is not task() for t in cp._kept_tasks)
    gc.collect()
    assert task() is None, "a finished handler is still held"


async def test_instance_discovery_follows_lease(cluster):
    worker_rt, client_rt = cluster
    ep_w = worker_rt.namespace("ns").component("c").endpoint("d")
    handle = await ep_w.serve_endpoint(counting_handler)

    client = await client_rt.namespace("ns").component("c").endpoint("d").client().start()
    await client.wait_for_instances(timeout=5)
    assert client.instance_ids() == [handle.lease_id]

    await handle.stop()
    for _ in range(50):
        if not client.instance_ids():
            break
        await asyncio.sleep(0.1)
    assert client.instance_ids() == []
    await client.stop()


async def test_direct_routing(local_rt):
    ep = local_rt.namespace("ns").component("c").endpoint("multi")
    lease_a = await local_rt.plane.lease_create(30)
    lease_b = await local_rt.plane.lease_create(30)

    async def tagged(tag):
        async def h(request, ctx):
            yield tag

        return h

    ha = await ep.serve_endpoint(await tagged("a"), lease_id=lease_a)
    hb = await ep.serve_endpoint(await tagged("b"), lease_id=lease_b)
    client = await ep.client().start()
    await client.wait_for_instances(timeout=5)
    assert set(client.instance_ids()) == {lease_a, lease_b}

    sa = await client.generate({}, mode="direct", instance_id=lease_a)
    assert [x async for x in sa] == ["a"]
    sb = await client.generate({}, mode="direct", instance_id=lease_b)
    assert [x async for x in sb] == ["b"]
    await client.stop()
    await ha.stop()
    await hb.stop()


def test_traceparent_synthesis_and_child_spans():
    """W3C traceparent: synthesized when absent (trace id = request id),
    same trace id with a fresh span id per hop (ref:
    addressed_router.rs:144-167)."""
    from dynamo_tpu.runtime.context import Context

    ctx = Context()
    tp = ctx.ensure_traceparent()
    ver, trace_id, span_id, flags = tp.split("-")
    assert ver == "00" and len(trace_id) == 32 and len(span_id) == 16
    assert trace_id == ctx.id  # uuid4 hex doubles as the trace id

    # wire hop: same trace, new span
    wire = ctx.to_wire()
    ver2, trace2, span2, _ = wire["traceparent"].split("-")
    assert trace2 == trace_id and span2 != span_id

    # an incoming traceparent is preserved, not replaced
    ctx2 = Context(traceparent="00-" + "a" * 32 + "-" + "b" * 16 + "-01")
    assert ctx2.ensure_traceparent().split("-")[1] == "a" * 32
    assert Context.from_wire(ctx2.to_wire()).traceparent.split("-")[1] == "a" * 32


def test_context_tenant_priority_wire_roundtrip(caplog):
    """QoS wire fields (docs/qos.md): tenant/priority survive
    to_wire/from_wire, a legacy peer that sends NEITHER gets defaults with
    no KeyError (and emits neither key back), and a malformed priority
    string falls back to the default class with a warning."""
    import logging

    from dynamo_tpu.runtime.context import Context

    ctx = Context(tenant="acme", priority="batch")
    ctx.set_timeout_ms(5000)
    back = Context.from_wire(ctx.to_wire())
    assert back.tenant == "acme" and back.priority == "batch"
    assert back.remaining_s() is not None  # deadline rides along unchanged
    # child contexts keep the QoS identity (worker-side hops)
    assert ctx.child().tenant == "acme" and ctx.child().priority == "batch"

    # legacy peer: both fields absent — defaults applied, no KeyError,
    # and the reply wire stays clean of keys the peer never sent
    legacy = Context.from_wire({"id": "req-1", "annotations": {"k": "v"}})
    assert legacy.tenant is None and legacy.priority is None
    assert "tenant" not in legacy.to_wire()
    assert "priority" not in legacy.to_wire()
    assert legacy.annotations == {"k": "v"}

    # malformed priority: fallback + warning, never a failed request
    with caplog.at_level(logging.WARNING, logger="dynamo.qos"):
        bad = Context.from_wire({"id": "req-2", "priority": "ultra!!"})
    assert bad.priority == "standard"
    assert any("ultra!!" in r.message for r in caplog.records)


def test_runtime_config_layering(tmp_path):
    """defaults < config file < DYN_* env, typed coercion, loud failures
    (ref: config.rs:1-608 figment layering)."""
    import pytest as _pytest

    from dynamo_tpu.runtime.config import ConfigError, RuntimeConfig

    # defaults
    cfg = RuntimeConfig.load(env={})
    assert cfg.lease_ttl == 10.0 and cfg.namespace == "dynamo"
    assert cfg.control_plane_address is None

    # file layer
    f = tmp_path / "dyn.toml"
    f.write_text('lease_ttl = 5.0\nnamespace = "prod"\nsystem_port = 9100\n')
    cfg = RuntimeConfig.load(config_file=str(f), env={})
    assert cfg.lease_ttl == 5.0 and cfg.namespace == "prod"
    assert cfg.system_port == 9100

    # env overrides the file, strings coerce to the field types
    cfg = RuntimeConfig.load(config_file=str(f), env={
        "DYN_LEASE_TTL": "2.5", "DYN_CONTROL_PLANE": "10.0.0.1:2379",
        "DYN_HEALTH_CHECK_FAILURES": "7"})
    assert cfg.lease_ttl == 2.5 and cfg.namespace == "prod"
    assert cfg.control_plane_address == "10.0.0.1:2379"
    assert cfg.health_check_failures == 7

    # JSON files work too
    j = tmp_path / "dyn.json"
    j.write_text('{"request_timeout": 3.0}')
    assert RuntimeConfig.load(config_file=str(j), env={}).request_timeout == 3.0

    # typo'd file key fails loudly
    bad = tmp_path / "bad.toml"
    bad.write_text("leese_ttl = 5.0\n")
    with _pytest.raises(ConfigError, match="leese_ttl"):
        RuntimeConfig.load(config_file=str(bad), env={})

    # malformed value names the field
    with _pytest.raises(ConfigError, match="lease_ttl"):
        RuntimeConfig.load(env={"DYN_LEASE_TTL": "fast"})
    # validation: nonsense ranges rejected
    with _pytest.raises(ConfigError, match="lease_ttl"):
        RuntimeConfig.load(env={"DYN_LEASE_TTL": "-1"})


@pytest.mark.anyio
async def test_task_tracker_hierarchy_and_policies():
    """Structured concurrency (ref: utils/tasks/tracker.rs): error
    policies, child coverage, graceful join."""
    from dynamo_tpu.runtime.tasks import OnErrorPolicy, TaskTracker

    shutdowns = []
    root = TaskTracker("r", on_shutdown=lambda: shutdowns.append(1))
    child = root.child("c")
    ran = []

    async def ok(tag):
        ran.append(tag)

    async def boom():
        raise RuntimeError("kaboom")

    async def forever():
        await asyncio.sleep(3600)

    # CONTINUE: failure logged, siblings unaffected
    t1 = child.spawn(ok("a"))
    t2 = child.spawn(boom(), "boom", OnErrorPolicy.CONTINUE)
    await asyncio.gather(t1, t2, return_exceptions=True)
    assert ran == ["a"] and child.errors == 1

    # CANCEL_SCOPE: failure cancels the tracker's other tasks
    scope = root.child("scope")
    hang = scope.spawn(forever(), "hang")
    bad = scope.spawn(boom(), "boom", OnErrorPolicy.CANCEL_SCOPE)
    await asyncio.gather(hang, bad, return_exceptions=True)
    assert hang.cancelled()

    # SHUTDOWN bubbles to the root callback from a grandchild
    gc = child.child("gc")
    t = gc.spawn(boom(), "critical", OnErrorPolicy.SHUTDOWN)
    await asyncio.gather(t, return_exceptions=True)
    assert shutdowns == [1]

    # join drains children and cancels stragglers; refuses new spawns
    s = root.child("drain")
    slow = s.spawn(forever(), "slow")
    await root.join(graceful_timeout=0.05)
    assert slow.cancelled()
    with pytest.raises(RuntimeError, match="closed"):
        root.spawn(ok("x"))
    assert root.inflight == 0


@pytest.mark.anyio
async def test_task_tracker_concurrency_bound():
    from dynamo_tpu.runtime.tasks import TaskTracker

    tr = TaskTracker("b", max_concurrency=2)
    active = 0
    peak = 0

    async def work():
        nonlocal active, peak
        active += 1
        peak = max(peak, active)
        await asyncio.sleep(0.02)
        active -= 1

    await asyncio.gather(*[tr.spawn(work()) for _ in range(8)])
    assert peak <= 2


@pytest.mark.anyio
async def test_task_tracker_join_covers_grandchildren():
    """join() drains the WHOLE subtree, not only direct children."""
    from dynamo_tpu.runtime.tasks import TaskTracker

    root = TaskTracker("r")
    gc = root.child("c").child("gc")

    async def forever():
        await asyncio.sleep(3600)

    t = gc.spawn(forever(), "deep")
    await root.join(graceful_timeout=0.05)
    assert t.cancelled()
    with pytest.raises(RuntimeError, match="closed"):
        gc.spawn(forever())


def test_runtime_config_null_rejected(tmp_path):
    import pytest as _pytest

    from dynamo_tpu.runtime.config import ConfigError, RuntimeConfig

    j = tmp_path / "n.json"
    j.write_text('{"namespace": null}')
    with _pytest.raises(ConfigError, match="namespace"):
        RuntimeConfig.load(config_file=str(j), env={})
    with _pytest.raises(ConfigError, match="health_check_interval"):
        RuntimeConfig.load(env={"DYN_HEALTH_CHECK_INTERVAL": "0"})


async def test_worker_monitor_busy_routing(local_rt):
    """WorkerMonitor (ref: worker_monitor.rs): a KV-saturated worker is
    skipped by routing until its load drops; all-busy degrades to routing
    anyway (backpressure, not failure)."""
    import msgpack

    from dynamo_tpu.llm.model_card import MODEL_ROOT
    from dynamo_tpu.router.protocols import (
        ForwardPassMetrics, KvStats, KV_METRICS_SUBJECT,
    )
    from dynamo_tpu.runtime.worker_monitor import WorkerMonitor

    ep = local_rt.namespace("ns").component("comp").endpoint("gen")
    hits: list[int] = []

    def make_handler(tag):
        async def handler(request, ctx=None):
            hits.append(tag)
            yield {"ok": tag}
        return handler

    l1 = await local_rt.plane.lease_create(ttl=10.0)
    l2 = await local_rt.plane.lease_create(ttl=10.0)
    h1 = await ep.serve_endpoint(make_handler(1), lease_id=l1)
    h2 = await ep.serve_endpoint(make_handler(2), lease_id=l2)
    client = await ep.client().start()
    ids = await client.wait_for_instances(timeout=5)
    assert len(ids) == 2

    # register each worker's capacity under models/ (what register_llm does)
    for iid in ids:
        await local_rt.plane.kv_put(
            f"{MODEL_ROOT}/m/{iid:x}",
            msgpack.packb({"name": "m", "instance_id": iid,
                           "card": {"display_name": "m",
                                    "runtime_config": {"total_kv_blocks": 100}}}))
    mon = await WorkerMonitor(client, busy_threshold=0.9).start()
    try:
        async def publish_load(iid, active):
            await local_rt.plane.publish(KV_METRICS_SUBJECT, msgpack.packb({
                "worker_id": iid,
                "metrics": ForwardPassMetrics(
                    kv_stats=KvStats(kv_active_blocks=active,
                                     kv_total_blocks=100)).to_wire()}))

        # worker ids[0] saturated (95 > 0.9*100), ids[1] light
        await publish_load(ids[0], 95)
        await publish_load(ids[1], 10)
        for _ in range(100):
            if client.available_ids() == [ids[1]]:
                break
            await asyncio.sleep(0.01)
        assert client.available_ids() == [ids[1]]

        hits.clear()
        for _ in range(4):
            recv = await client.generate({"n": 1}, mode="round_robin")
            async for _ in recv:
                pass
        assert set(hits) == {2}  # all routed to the light worker

        # both saturated → degrade to routing anyway (never NoResponders)
        await publish_load(ids[1], 99)
        for _ in range(100):
            if mon._busy == sorted(ids):
                break
            await asyncio.sleep(0.01)
        assert sorted(client.available_ids()) == sorted(ids)

        # load drops → busy clears
        await publish_load(ids[0], 5)
        await publish_load(ids[1], 5)
        for _ in range(100):
            if not mon._busy:
                break
            await asyncio.sleep(0.01)
        assert sorted(client.available_ids()) == sorted(ids)
    finally:
        await mon.stop()
        await h1.stop(graceful=False)
        await h2.stop(graceful=False)


def test_busy_threshold_config_layering(monkeypatch):
    """DYN_BUSY_THRESHOLD rides the layered RuntimeConfig like every other
    DYN_* knob — validated, not a bare float() at the call site."""
    import pytest as _pytest

    from dynamo_tpu.runtime.config import ConfigError, RuntimeConfig

    assert RuntimeConfig.load(env={}).busy_threshold is None
    assert RuntimeConfig.load(env={"DYN_BUSY_THRESHOLD": "0.9"}).busy_threshold == 0.9
    with _pytest.raises(ConfigError):
        RuntimeConfig.load(env={"DYN_BUSY_THRESHOLD": "abc"})
    with _pytest.raises(ConfigError):
        RuntimeConfig.load(env={"DYN_BUSY_THRESHOLD": "1.5"})
