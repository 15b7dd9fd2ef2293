"""Control plane semantics: KV/leases/watches, pub-sub, request/reply, streams.

Covers both the in-process plane and the TCP server+client pair with the same
assertions (parity by construction is still verified by test).
"""

import asyncio

import pytest

from dynamo_tpu.runtime.control_plane import (
    ControlPlaneServer,
    LocalControlPlane,
    NoRespondersError,
    RemoteControlPlane,
)


@pytest.fixture(params=["local", "remote"])
async def plane(request):
    if request.param == "local":
        p = LocalControlPlane()
        yield p
        await p.close()
    else:
        server = ControlPlaneServer()
        addr = await server.start()
        p = await RemoteControlPlane(addr).connect()
        yield p
        await p.close()
        await server.stop()


pytestmark = pytest.mark.anyio


async def test_kv_basic(plane):
    await plane.kv_put("foo/a", b"1")
    await plane.kv_put("foo/b", b"2")
    assert await plane.kv_get("foo/a") == b"1"
    assert await plane.kv_get("nope") is None
    assert await plane.kv_get_prefix("foo/") == {"foo/a": b"1", "foo/b": b"2"}
    assert await plane.kv_create("foo/a", b"x") is False
    assert await plane.kv_create("foo/c", b"3") is True
    assert await plane.kv_delete("foo/a") == 1
    assert await plane.kv_delete("foo/a") == 0
    assert await plane.kv_delete_prefix("foo/") == 2


async def test_watch_prefix(plane):
    await plane.kv_put("w/1", b"a")
    watch = await plane.watch_prefix("w/")
    assert watch.snapshot == {"w/1": b"a"}
    await plane.kv_put("w/2", b"b")
    await plane.kv_delete("w/1")
    it = watch.__aiter__()
    ev1 = await asyncio.wait_for(it.__anext__(), 5)
    assert (ev1.type, ev1.key, ev1.value) == ("put", "w/2", b"b")
    ev2 = await asyncio.wait_for(it.__anext__(), 5)
    assert (ev2.type, ev2.key) == ("delete", "w/1")
    await watch.cancel()


async def test_lease_attach_and_revoke(plane):
    lease = await plane.lease_create(ttl=30)
    await plane.kv_put("lease/a", b"1", lease_id=lease)
    watch = await plane.watch_prefix("lease/")
    await plane.lease_revoke(lease)
    it = watch.__aiter__()
    ev = await asyncio.wait_for(it.__anext__(), 5)
    assert (ev.type, ev.key) == ("delete", "lease/a")
    assert await plane.kv_get("lease/a") is None
    await watch.cancel()


async def test_lease_keepalive(plane):
    lease = await plane.lease_create(ttl=30)
    assert await plane.lease_keepalive(lease) is True
    await plane.lease_revoke(lease)
    assert await plane.lease_keepalive(lease) is False


async def test_pubsub(plane):
    sub = await plane.subscribe("events.>")
    await plane.publish("events.a", b"1")
    await plane.publish("other", b"x")
    await plane.publish("events.b", b"2")
    it = sub.__aiter__()
    assert await asyncio.wait_for(it.__anext__(), 5) == ("events.a", b"1")
    assert await asyncio.wait_for(it.__anext__(), 5) == ("events.b", b"2")
    await sub.cancel()


async def test_request_reply(plane):
    async def handler(payload: bytes) -> bytes:
        return b"echo:" + payload

    cancel = await plane.serve("svc.echo", handler)
    assert await plane.request("svc.echo", b"hi") == b"echo:hi"
    await cancel()
    with pytest.raises(NoRespondersError):
        await plane.request("svc.echo", b"hi")


async def test_request_no_responders(plane):
    with pytest.raises(NoRespondersError):
        await plane.request("nobody.home", b"x")


async def test_durable_stream(plane):
    s1 = await plane.stream_publish("kv_events", b"e1")
    s2 = await plane.stream_publish("kv_events", b"e2")
    assert s2 == s1 + 1
    # late subscriber replays from offset
    sub = await plane.stream_subscribe("kv_events", start_seq=0)
    it = sub.__aiter__()
    assert await asyncio.wait_for(it.__anext__(), 5) == (s1, b"e1")
    assert await asyncio.wait_for(it.__anext__(), 5) == (s2, b"e2")
    s3 = await plane.stream_publish("kv_events", b"e3")
    assert await asyncio.wait_for(it.__anext__(), 5) == (s3, b"e3")
    assert await plane.stream_last_seq("kv_events") == s3
    await sub.cancel()


async def test_object_store(plane):
    await plane.object_put("radix-bucket", "snap", b"\x00\x01")
    assert await plane.object_get("radix-bucket", "snap") == b"\x00\x01"
    assert await plane.object_get("radix-bucket", "missing") is None


async def test_lease_expiry_local():
    plane = LocalControlPlane()
    lease = await plane.lease_create(ttl=0.2)
    await plane.kv_put("exp/a", b"1", lease_id=lease)
    await asyncio.sleep(1.6)
    assert await plane.kv_get("exp/a") is None
    await plane.close()


async def test_a_frozen_hub_does_not_expire_the_leases_it_could_not_renew():
    """A host that freezes past the TTL stops holders and hub alike; the
    sweeper wakes first, and must leave the holders time to renew."""
    import time

    plane = LocalControlPlane()
    lease = await plane.lease_create(ttl=0.5)
    await plane.kv_put("frozen/a", b"1", lease_id=lease)
    await asyncio.sleep(0.1)          # the sweeper is asleep in its interval
    time.sleep(2.5)                   # nothing runs: not the hub, not us
    await asyncio.sleep(0.05)         # the sweeper wakes before we renew
    assert await plane.lease_keepalive(lease) is True
    assert await plane.kv_get("frozen/a") == b"1"
    await asyncio.sleep(1.7)          # and a holder that stays away expires
    assert await plane.kv_get("frozen/a") is None
    await plane.close()


async def test_remote_disconnect_revokes_lease():
    server = ControlPlaneServer()
    addr = await server.start()
    p = await RemoteControlPlane(addr).connect()
    lease = await p.lease_create(ttl=300)
    await p.kv_put("dc/a", b"1", lease_id=lease)
    await p.close()
    for _ in range(50):
        if await server.core.kv_get("dc/a") is None:
            break
        await asyncio.sleep(0.1)
    assert await server.core.kv_get("dc/a") is None
    await server.stop()


async def test_work_queue_semantics():
    """push/pop order, exactly-one delivery, block-until-push, timeout."""
    plane = LocalControlPlane()
    await plane.queue_push("q", b"a")
    await plane.queue_push("q", b"b")
    assert await plane.queue_depth("q") == 2
    assert await plane.queue_pop("q") == b"a"
    assert await plane.queue_pop("q") == b"b"
    assert await plane.queue_depth("q") == 0
    # timeout with nothing queued
    assert await plane.queue_pop("q", timeout=0.05) is None
    # blocked popper woken by push; each item delivered exactly once
    pops = [asyncio.create_task(plane.queue_pop("q", timeout=5.0))
            for _ in range(2)]
    await asyncio.sleep(0.02)
    await plane.queue_push("q", b"x")
    await plane.queue_push("q", b"y")
    got = sorted(await asyncio.gather(*pops))
    assert got == [b"x", b"y"]
    await plane.close()


async def test_work_queue_cross_process_semantics():
    """Same semantics through the TCP server/remote client pair."""
    server = ControlPlaneServer(port=0)
    addr = await server.start()
    a = await RemoteControlPlane(addr).connect()
    b = await RemoteControlPlane(addr).connect()
    try:
        pop = asyncio.create_task(a.queue_pop("jobs", timeout=5.0))
        await asyncio.sleep(0.05)
        await b.queue_push("jobs", b"ticket")
        assert await pop == b"ticket"
        await b.queue_push("jobs", b"t2")
        assert await a.queue_depth("jobs") == 1
        assert await b.queue_pop("jobs", timeout=1.0) == b"t2"
    finally:
        await a.close()
        await b.close()
        await server.stop()
