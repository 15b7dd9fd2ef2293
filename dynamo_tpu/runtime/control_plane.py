"""Self-contained control plane with etcd + NATS semantics.

The reference runtime leans on two external services (SURVEY.md §2.1): etcd for
discovery/leases/watches (ref: lib/runtime/src/transports/etcd.rs:35) and NATS
for the request plane, events, queues and object store (ref: transports/
nats.rs:48,426). A TPU-VM pod should not need either, so this module provides
one service — ``dynctl`` — with both semantic sets:

- **KV + leases + prefix watches** (etcd): ``kv_put/kv_create/kv_get/
  kv_get_prefix/kv_delete``, leases with TTL + keepalive whose expiry deletes
  attached keys and fires watch delete events.
- **Pub/sub + request/reply** (NATS core): subjects with optional queue
  groups; ``request`` raises :class:`NoRespondersError` when nothing serves
  the subject — the same signal the reference uses for instant fault
  detection (ref: pipeline/network/egress/push_router.rs:229).
- **Durable streams + object store** (NATS JetStream): append-only logs with
  consumer offsets (KV events ride these) and a bucket/name byte store
  (radix snapshots).

Two interchangeable implementations: :class:`LocalControlPlane` (pure
in-process asyncio — used single-process and as the server's core) and
:class:`RemoteControlPlane` (TCP client to a :class:`ControlPlaneServer`).
Because the server *wraps* a LocalControlPlane, cross-process behavior is
identical to in-process behavior by construction.
"""

from __future__ import annotations

import abc
import asyncio
import logging
import os
import random
import time

import msgpack
from collections import deque
from dataclasses import dataclass, field
from typing import AsyncIterator, Awaitable, Callable, Optional

from dynamo_tpu.runtime.chaos import get_chaos
from dynamo_tpu.runtime.codec import close_server, read_frame, write_frame

logger = logging.getLogger("dynamo.control_plane")

DEFAULT_LEASE_TTL = 10.0
SWEEP_INTERVAL = 1.0
STREAM_MAX_LEN = 65536  # per-stream ring buffer cap
# In-band stream discontinuity marker (see RemoteControlPlane._replay): real
# stream seqs are >= 1, so a negative seq can never collide with one.
EPOCH_MARKER_SEQ = -1


#: handler tasks spawned by a connection's read loop, held until they finish
_kept_tasks: set = set()


def _spawn_kept(coro) -> asyncio.Task:
    """``create_task`` that keeps the task alive. The event loop holds only a
    weak reference to a task, so one whose caller drops the result can be
    garbage-collected while it waits: a handler that never answers (a
    dispatch ack lost, its request waiting out the 10 s ``request_timeout``).
    The task is released when it is done."""
    task = asyncio.get_running_loop().create_task(coro)
    _kept_tasks.add(task)
    task.add_done_callback(_kept_tasks.discard)
    return task


class NoRespondersError(Exception):
    """No service instance is listening on the requested subject."""


class ControlPlaneClosed(Exception):
    pass


@dataclass(frozen=True)
class WatchEvent:
    type: str  # "put" | "delete"
    key: str
    value: bytes = b""


class Watch:
    """Prefix watch: a snapshot plus a live event queue."""

    def __init__(self, snapshot: dict[str, bytes], queue: "asyncio.Queue[Optional[WatchEvent]]", cancel):
        self.snapshot = snapshot
        self._queue = queue
        self._cancel = cancel

    def __aiter__(self) -> AsyncIterator[WatchEvent]:
        return self._iter()

    async def _iter(self):
        while True:
            ev = await self._queue.get()
            if ev is None:
                return
            yield ev

    async def cancel(self) -> None:
        await self._cancel()


class Subscription:
    """Pub/sub subscription handle yielding ``(subject, payload)``."""

    def __init__(self, queue: "asyncio.Queue[Optional[tuple[str, bytes]]]", cancel):
        self._queue = queue
        self._cancel = cancel

    def __aiter__(self):
        return self._iter()

    async def _iter(self):
        while True:
            item = await self._queue.get()
            if item is None:
                return
            yield item

    async def cancel(self) -> None:
        await self._cancel()


class StreamSub:
    """Durable-stream subscription yielding ``(seq, payload)`` from a start offset."""

    def __init__(self, queue: "asyncio.Queue[Optional[tuple[int, bytes]]]", cancel):
        self._queue = queue
        self._cancel = cancel

    def __aiter__(self):
        return self._iter()

    async def _iter(self):
        while True:
            item = await self._queue.get()
            if item is None:
                return
            yield item

    async def cancel(self) -> None:
        await self._cancel()


ServiceHandler = Callable[[bytes], Awaitable[bytes]]


class ControlPlane(abc.ABC):
    """Abstract control-plane client surface. All methods are coroutine-safe."""

    # -- KV (etcd semantics) --
    @abc.abstractmethod
    async def kv_put(self, key: str, value: bytes, lease_id: Optional[int] = None) -> None: ...

    @abc.abstractmethod
    async def kv_create(self, key: str, value: bytes, lease_id: Optional[int] = None) -> bool:
        """Create-if-absent; returns False when the key already exists."""

    @abc.abstractmethod
    async def kv_get(self, key: str) -> Optional[bytes]: ...

    @abc.abstractmethod
    async def kv_get_prefix(self, prefix: str) -> dict[str, bytes]: ...

    @abc.abstractmethod
    async def kv_delete(self, key: str) -> int: ...

    @abc.abstractmethod
    async def kv_delete_prefix(self, prefix: str) -> int: ...

    @abc.abstractmethod
    async def watch_prefix(self, prefix: str) -> Watch: ...

    # -- Leases --
    @abc.abstractmethod
    async def lease_create(self, ttl: float = DEFAULT_LEASE_TTL) -> int: ...

    @abc.abstractmethod
    async def lease_keepalive(self, lease_id: int) -> bool: ...

    @abc.abstractmethod
    async def lease_revoke(self, lease_id: int) -> None: ...

    # -- Pub/sub + request/reply (NATS semantics) --
    @abc.abstractmethod
    async def publish(self, subject: str, payload: bytes) -> None: ...

    @abc.abstractmethod
    async def subscribe(self, subject: str, queue_group: Optional[str] = None) -> Subscription: ...

    @abc.abstractmethod
    async def request(self, subject: str, payload: bytes, timeout: float = 30.0) -> bytes: ...

    @abc.abstractmethod
    async def serve(self, subject: str, handler: ServiceHandler):
        """Register a request handler; returns an awaitable-cancel handle.

        Multiple registrations on one subject form an implicit queue group:
        ``request`` round-robins across them (NATS service semantics)."""

    # -- Work queues (NatsQueue semantics, ref: transports/nats.rs:426 —
    #    the global prefill queue rides this) --
    @abc.abstractmethod
    async def queue_push(self, queue: str, payload: bytes) -> None: ...

    @abc.abstractmethod
    async def queue_pop(self, queue: str, timeout: float = 30.0) -> Optional[bytes]:
        """Pop one item; blocks up to ``timeout``; None when nothing arrived.
        Each item is delivered to exactly one popper (work-queue semantics)."""

    @abc.abstractmethod
    async def queue_depth(self, queue: str) -> int: ...

    # -- Durable streams (JetStream semantics) --
    @abc.abstractmethod
    async def stream_publish(self, stream: str, payload: bytes) -> int: ...

    @abc.abstractmethod
    async def stream_subscribe(self, stream: str, start_seq: int = 0) -> StreamSub: ...

    @abc.abstractmethod
    async def stream_last_seq(self, stream: str) -> int: ...

    @abc.abstractmethod
    async def stream_first_seq(self, stream: str) -> int:
        """Oldest seq still retained (ring truncation floor). A consumer whose
        last applied seq is < first_seq-1 has provably missed events and must
        resync (ref: JetStream stream FirstSeq, kv_router/subscriber.rs:30-65)."""

    # -- Object store --
    @abc.abstractmethod
    async def object_put(self, bucket: str, name: str, data: bytes) -> None: ...

    @abc.abstractmethod
    async def object_get(self, bucket: str, name: str) -> Optional[bytes]: ...

    @abc.abstractmethod
    async def object_delete(self, bucket: str, name: str) -> None: ...

    @abc.abstractmethod
    async def close(self) -> None: ...


# --------------------------------------------------------------------------
# Local (in-process) implementation
# --------------------------------------------------------------------------


@dataclass
class _Lease:
    id: int
    ttl: float
    deadline: float
    keys: set[str] = field(default_factory=set)
    owner: Optional[object] = None  # connection tag for revoke-on-disconnect


@dataclass
class _ServiceReg:
    subject: str
    handler: ServiceHandler
    owner: Optional[object] = None


def _subject_matches(pattern: str, subject: str) -> bool:
    """NATS-style: exact match, or trailing ``>`` matches any suffix."""
    if pattern.endswith(">"):
        return subject.startswith(pattern[:-1])
    return pattern == subject


class _HubHist:
    """Tiny fixed-bucket latency histogram for hub self-instrumentation —
    runtime.metrics.Histogram carries labels/locks this single-loop hot
    path does not need. Rendered as ``dynamo_hub_publish_seconds`` by the
    metrics aggregator (metrics/main.py)."""

    BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 1e-1)

    __slots__ = ("counts", "sum", "count")

    def __init__(self):
        self.counts = [0] * (len(self.BUCKETS) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        self.sum += v
        self.count += 1
        for i, b in enumerate(self.BUCKETS):
            if v <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def to_dict(self) -> dict:
        cum, buckets = 0, {}
        for i, b in enumerate(self.BUCKETS):
            cum += self.counts[i]
            buckets[str(b)] = cum
        buckets["+Inf"] = self.count
        return {"count": self.count, "sum": self.sum, "buckets": buckets}


class LocalControlPlane(ControlPlane):
    """In-process control plane; also the core of :class:`ControlPlaneServer`."""

    def __init__(self, stream_max_len: int = STREAM_MAX_LEN):
        #: identifies this hub incarnation: stream seqs are only comparable
        #: within one epoch (clients resume from 0 after a hub restart)
        self.epoch = f"{random.getrandbits(64):016x}"
        self.stream_max_len = stream_max_len
        self._kv: dict[str, bytes] = {}
        self._key_lease: dict[str, int] = {}
        self._leases: dict[int, _Lease] = {}
        self._next_lease = int(time.time() * 1000) << 16 | random.getrandbits(16)
        self._watches: list[tuple[str, asyncio.Queue]] = []
        self._subs: list[tuple[str, Optional[str], asyncio.Queue]] = []
        self._services: list[_ServiceReg] = []
        self._rr: dict[str, int] = {}
        self._streams: dict[str, tuple[int, list[tuple[int, bytes]]]] = {}  # first_seq offset handling
        self._stream_subs: dict[str, list[asyncio.Queue]] = {}
        self._queues: dict[str, "deque[bytes]"] = {}
        self._queue_waiters: dict[str, "deque[asyncio.Future]"] = {}
        self._objects: dict[tuple[str, str], bytes] = {}
        self._closed = False
        self._sweeper: Optional[asyncio.Task] = None
        #: hub self-instrumentation (docs/observability.md): per-op event
        #: counters + event-path publish latency, the measured series
        #: behind the fleet-bench batching ceiling (docs/PERF_NOTES.md) —
        #: read via hub_stats() / the `hub_stats` wire op
        self.hub_events: dict[str, int] = {}
        self.hub_publish = _HubHist()
        #: per-stream entries dropped off the ring cap — a consumer
        #: further behind than this sees a gap and must resync
        self.hub_stream_truncated: dict[str, int] = {}
        #: resync requests observed (publishes on the kv_resync.* subject
        #: — the literal prefix is a wire constant, router/protocols.py's
        #: KV_RESYNC_SUBJECT; importing it here would cycle the packages)
        self.hub_resyncs_requested = 0

    def _ensure_sweeper(self):
        if self._sweeper is None or self._sweeper.done():
            self._sweeper = asyncio.get_running_loop().create_task(self._sweep_loop())

    async def _sweep_loop(self):
        """Revoke leases past their deadline. Time in which this process
        itself did not run (a frozen host: every process stops, the clock
        does not) is not held against the holders: nobody could have
        renewed, and their keepalives arrive right after the sweeper wakes.
        Every lease gets a fresh TTL from the waking (as a new etcd leader
        grants); a holder that is really gone expires one TTL later."""
        try:
            woke = time.monotonic()
            while not self._closed:
                await asyncio.sleep(SWEEP_INTERVAL)
                now = time.monotonic()
                overrun, woke = now - woke - SWEEP_INTERVAL, now
                if overrun > SWEEP_INTERVAL:
                    logger.warning("hub did not run for %.1f s: every lease "
                                   "gets a fresh TTL", overrun)
                    for lease in self._leases.values():
                        lease.deadline = max(lease.deadline, now + lease.ttl)
                expired = [l.id for l in self._leases.values() if l.deadline < now]
                for lid in expired:
                    logger.info("lease %x expired", lid)
                    await self.lease_revoke(lid)
        except asyncio.CancelledError:
            pass

    def _hub_count(self, kind: str) -> None:
        self.hub_events[kind] = self.hub_events.get(kind, 0) + 1

    async def hub_stats(self) -> dict:
        """Event counters + publish latency for dynctl top and the metrics
        aggregator's dynamo_hub_* series — plus per-stream health (last
        seq / first retained seq / entries truncated off the ring) and
        the resync-request count, so the `dynctl top` hub footer shows
        whether the KV event stream is outrunning its consumers."""
        streams = {}
        for name, (seq, entries) in self._streams.items():
            streams[name] = {
                "last_seq": seq,
                "first_seq": entries[0][0] if entries else seq + 1,
                "truncated": self.hub_stream_truncated.get(name, 0),
            }
        return {"epoch": self.epoch, "events": dict(self.hub_events),
                "publish_seconds": self.hub_publish.to_dict(),
                "streams": streams,
                "resyncs_requested": self.hub_resyncs_requested}

    # -- KV --
    def _notify(self, ev: WatchEvent):
        for prefix, q in self._watches:
            if ev.key.startswith(prefix):
                q.put_nowait(ev)

    async def kv_put(self, key, value, lease_id=None):
        self._hub_count("kv_put")
        self._kv[key] = value
        self._attach_lease(key, lease_id)
        self._notify(WatchEvent("put", key, value))

    def _attach_lease(self, key: str, lease_id: Optional[int]):
        old = self._key_lease.pop(key, None)
        if old is not None and old in self._leases:
            self._leases[old].keys.discard(key)
        if lease_id is not None:
            lease = self._leases.get(lease_id)
            if lease is None:
                raise ValueError(f"unknown lease {lease_id:#x}")
            lease.keys.add(key)
            self._key_lease[key] = lease_id

    async def kv_create(self, key, value, lease_id=None) -> bool:
        if key in self._kv:
            return False
        await self.kv_put(key, value, lease_id)
        return True

    async def kv_get(self, key):
        return self._kv.get(key)

    async def kv_get_prefix(self, prefix):
        return {k: v for k, v in self._kv.items() if k.startswith(prefix)}

    async def kv_delete(self, key) -> int:
        self._hub_count("kv_delete")
        if key in self._kv:
            del self._kv[key]
            self._attach_lease(key, None)
            self._notify(WatchEvent("delete", key))
            return 1
        return 0

    async def kv_delete_prefix(self, prefix) -> int:
        keys = [k for k in self._kv if k.startswith(prefix)]
        for k in keys:
            await self.kv_delete(k)
        return len(keys)

    async def watch_prefix(self, prefix) -> Watch:
        q: asyncio.Queue = asyncio.Queue()
        entry = (prefix, q)
        self._watches.append(entry)
        snapshot = await self.kv_get_prefix(prefix)

        async def cancel():
            if entry in self._watches:
                self._watches.remove(entry)
            q.put_nowait(None)

        return Watch(snapshot, q, cancel)

    # -- Leases --
    async def lease_create(self, ttl=DEFAULT_LEASE_TTL, owner=None) -> int:
        self._ensure_sweeper()
        self._next_lease += 1
        lid = self._next_lease
        self._leases[lid] = _Lease(lid, ttl, time.monotonic() + ttl, owner=owner)
        return lid

    async def lease_keepalive(self, lease_id) -> bool:
        lease = self._leases.get(lease_id)
        if lease is None:
            return False
        lease.deadline = time.monotonic() + lease.ttl
        return True

    async def lease_revoke(self, lease_id):
        lease = self._leases.pop(lease_id, None)
        if lease is None:
            return
        for key in list(lease.keys):
            await self.kv_delete(key)

    async def revoke_owned(self, owner):
        """Drop every lease/service/sub owned by a disconnected remote client."""
        for lid in [l.id for l in self._leases.values() if l.owner is owner]:
            await self.lease_revoke(lid)
        self._services = [s for s in self._services if s.owner is not owner]

    # -- Pub/sub --
    async def publish(self, subject, payload):
        self._hub_count("publish")
        if subject.startswith("kv_resync"):
            self.hub_resyncs_requested += 1
        chaos = get_chaos()
        if chaos is not None:
            await chaos.pre("plane.publish")
            if chaos.should_drop("plane.publish"):
                return  # message loss: subscribers simply never see it
        t0 = time.perf_counter()
        groups: dict[str, list[asyncio.Queue]] = {}
        for pattern, qg, q in self._subs:
            if _subject_matches(pattern, subject):
                if qg is None:
                    q.put_nowait((subject, payload))
                else:
                    groups.setdefault(qg, []).append(q)
        for qs in groups.values():
            random.choice(qs).put_nowait((subject, payload))
        self.hub_publish.observe(time.perf_counter() - t0)

    async def subscribe(self, subject, queue_group=None) -> Subscription:
        q: asyncio.Queue = asyncio.Queue()
        entry = (subject, queue_group, q)
        self._subs.append(entry)

        async def cancel():
            if entry in self._subs:
                self._subs.remove(entry)
            q.put_nowait(None)

        return Subscription(q, cancel)

    # -- Request/reply --
    async def request(self, subject, payload, timeout=30.0) -> bytes:
        self._hub_count("request")
        regs = [s for s in self._services if _subject_matches(s.subject, subject)]
        if not regs:
            raise NoRespondersError(subject)
        idx = self._rr.get(subject, 0)
        self._rr[subject] = idx + 1
        reg = regs[idx % len(regs)]
        return await asyncio.wait_for(reg.handler(payload), timeout)

    async def serve(self, subject, handler, owner=None):
        reg = _ServiceReg(subject, handler, owner)
        self._services.append(reg)

        async def cancel():
            if reg in self._services:
                self._services.remove(reg)

        return cancel

    def has_responder(self, subject: str) -> bool:
        return any(_subject_matches(s.subject, subject) for s in self._services)

    # -- Work queues --
    QUEUE_MAX_LEN = 65536  # oldest tickets dropped past this (cap like streams)

    async def queue_push(self, queue, payload) -> None:
        self._hub_count("queue_push")
        waiters = self._queue_waiters.get(queue)
        while waiters:
            fut = waiters.popleft()
            if not fut.done():  # hand straight to a blocked popper
                fut.set_result(payload)
                return
        q = self._queues.setdefault(queue, deque())
        q.append(payload)
        while len(q) > self.QUEUE_MAX_LEN:
            q.popleft()

    async def queue_pop(self, queue, timeout: float = 30.0) -> Optional[bytes]:
        q = self._queues.get(queue)
        if q:
            return q.popleft()
        fut = asyncio.get_running_loop().create_future()
        waiters = self._queue_waiters.setdefault(queue, deque())
        waiters.append(fut)
        try:
            return await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            return None
        finally:
            # a timed-out waiter must not linger until the next push skims it
            try:
                waiters.remove(fut)
            except ValueError:
                pass

    async def queue_depth(self, queue) -> int:
        return len(self._queues.get(queue, ()))

    # -- Durable streams --
    async def stream_publish(self, stream, payload) -> int:
        self._hub_count("stream_publish")
        chaos = get_chaos()
        if chaos is not None:
            await chaos.pre("plane.publish")
            if chaos.should_drop("plane.publish"):
                # lost BEFORE the stream assigns a seq: no gap for the
                # consumer's sequence check to see — the silent-drift
                # shape the KV audit plane exists to catch
                # (docs/observability.md "KV audit")
                seq, _ = self._streams.get(stream, (0, []))
                return seq
        t0 = time.perf_counter()
        seq, entries = self._streams.get(stream, (0, []))
        seq += 1
        entries.append((seq, payload))
        if len(entries) > self.stream_max_len:
            dropped = len(entries) - self.stream_max_len
            self.hub_stream_truncated[stream] = (
                self.hub_stream_truncated.get(stream, 0) + dropped)
            entries[:] = entries[-self.stream_max_len:]
        self._streams[stream] = (seq, entries)
        for q in self._stream_subs.get(stream, []):
            q.put_nowait((seq, payload))
        self.hub_publish.observe(time.perf_counter() - t0)
        return seq

    async def stream_subscribe(self, stream, start_seq=0) -> StreamSub:
        q: asyncio.Queue = asyncio.Queue()
        _, entries = self._streams.get(stream, (0, []))
        for seq, payload in entries:
            if seq > start_seq:
                q.put_nowait((seq, payload))
        self._stream_subs.setdefault(stream, []).append(q)

        async def cancel():
            subs = self._stream_subs.get(stream, [])
            if q in subs:
                subs.remove(q)
            q.put_nowait(None)

        return StreamSub(q, cancel)

    async def stream_last_seq(self, stream) -> int:
        seq, _ = self._streams.get(stream, (0, []))
        return seq

    async def stream_first_seq(self, stream) -> int:
        seq, entries = self._streams.get(stream, (0, []))
        return entries[0][0] if entries else seq + 1

    async def get_epoch(self) -> str:
        return self.epoch

    # -- persistence (dynctl --persist) ---------------------------------
    #: stream entries retained in a snapshot — consumers further behind
    #: resync via the gap protocol (indexer stream_first_seq check), so a
    #: bounded snapshot is principled, not lossy-by-accident
    PERSIST_STREAM_TAIL = 4096

    def dump_state(self) -> bytes:
        """Durable subset of hub state. LEASED keys are excluded: their
        owners died with the old process and re-register under fresh
        leases — persisting them would resurrect ghost instances. The
        epoch is preserved so stream seqs stay comparable across the
        restart (consumers resume WITHOUT a false gap)."""
        kv = {k: v for k, v in self._kv.items() if k not in self._key_lease}
        streams = {
            name: [seq, [list(e) for e in entries[-self.PERSIST_STREAM_TAIL:]]]
            for name, (seq, entries) in self._streams.items()
        }
        objects = [[b, n, data] for (b, n), data in self._objects.items()]
        return msgpack.packb({"v": 1, "epoch": self.epoch, "kv": kv,
                              "streams": streams, "objects": objects})

    def load_state(self, data: bytes) -> None:
        d = msgpack.unpackb(data, raw=False)
        self.epoch = d["epoch"]
        self._kv.update(d.get("kv") or {})
        for name, (seq, entries) in (d.get("streams") or {}).items():
            self._streams[name] = (seq, [tuple(e) for e in entries])
        for b, n, obj in d.get("objects") or []:
            self._objects[(b, n)] = obj

    def replace_state(self, data: bytes) -> None:
        """Standby replication: mirror a primary's durable state wholesale
        (a standby serves no clients, so there are no watches/subs to
        notify — deleted keys must vanish, hence clear-then-load)."""
        self._kv.clear()
        self._streams.clear()
        self._objects.clear()
        self.load_state(data)

    # -- Object store --
    async def object_put(self, bucket, name, data):
        self._objects[(bucket, name)] = data

    async def object_get(self, bucket, name):
        return self._objects.get((bucket, name))

    async def object_delete(self, bucket, name):
        self._objects.pop((bucket, name), None)

    async def close(self):
        self._closed = True
        if self._sweeper:
            self._sweeper.cancel()
        for _, q in self._watches:
            q.put_nowait(None)
        for _, _, q in self._subs:
            q.put_nowait(None)
        for qs in self._stream_subs.values():
            for q in qs:
                q.put_nowait(None)
        for waiters in self._queue_waiters.values():
            for fut in waiters:
                if not fut.done():
                    fut.set_result(None)


# --------------------------------------------------------------------------
# TCP server + remote client
# --------------------------------------------------------------------------


class ControlPlaneServer:
    """``dynctl``: exposes a LocalControlPlane over TCP to many processes."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 persist_path: Optional[str] = None,
                 persist_interval: float = 5.0,
                 standby_of: Optional[str] = None,
                 takeover_after: float = 6.0,
                 replicate_interval: float = 1.0):
        self.core = LocalControlPlane()
        self._host = host
        self._port = port
        self._server: Optional[asyncio.base_events.Server] = None
        self._conns: set["_ServerConn"] = set()
        #: durable-state file (ref role: etcd's WAL + JetStream file store —
        #: discovery keys, object store, stream tails survive a hub restart;
        #: leases deliberately do NOT). None = in-memory only.
        self._persist_path = persist_path
        self._persist_interval = persist_interval
        self._persist_task: Optional[asyncio.Task] = None
        #: warm standby (ref role: etcd replication / clustered NATS —
        #: lib/runtime/src/transports/etcd.rs:35-770 rides an HA etcd
        #: cluster; dynctl gets a 2-node primary/standby analog): while
        #: ``standby_of`` is set the server rejects client ops, mirrors the
        #: primary's durable state every ``replicate_interval`` s, and
        #: promotes itself after ``takeover_after`` s of primary silence.
        self._standby_of = standby_of
        self._takeover_after = takeover_after
        self._replicate_interval = replicate_interval
        self._standby_task: Optional[asyncio.Task] = None
        self._fence_task: Optional[asyncio.Task] = None
        self.is_standby = standby_of is not None

    @property
    def address(self) -> str:
        return f"{self._host}:{self._port}"

    async def start(self) -> str:
        if self._persist_path and os.path.exists(self._persist_path):
            try:
                with open(self._persist_path, "rb") as f:
                    self.core.load_state(f.read())
                logger.info("control plane state restored from %s (epoch %s)",
                            self._persist_path, self.core.epoch)
            except Exception:
                logger.exception("state restore failed; starting fresh")
        self._server = await asyncio.start_server(self._on_conn, self._host, self._port)
        self._port = self._server.sockets[0].getsockname()[1]
        if self._persist_path:
            self._persist_task = asyncio.get_running_loop().create_task(
                self._persist_loop())
        if self.is_standby:
            self._standby_task = asyncio.get_running_loop().create_task(
                self._standby_loop())
        logger.info("control plane listening on %s%s", self.address,
                    " (standby)" if self.is_standby else "")
        return self.address

    async def _standby_loop(self):
        """Mirror the primary until it goes silent, then promote."""
        last_ok = time.monotonic()
        host, _, port = self._standby_of.rpartition(":")
        host, port = host or "127.0.0.1", int(port)
        reader = writer = None
        rid = 0
        try:
            while self.is_standby:
                try:
                    if writer is None:
                        reader, writer = await asyncio.wait_for(
                            asyncio.open_connection(host, port), 5.0)
                    rid += 1
                    await write_frame(writer, {"t": "req", "id": rid,
                                               "op": "dump_state"})
                    # private conn: the only traffic is our own responses
                    msg = await asyncio.wait_for(read_frame(reader), 10.0)
                    if not (msg.get("t") == "res" and msg.get("ok")):
                        raise RuntimeError(msg.get("detail", "pull failed"))
                    self.core.replace_state(msg["value"])
                    last_ok = time.monotonic()
                except asyncio.CancelledError:
                    raise
                except Exception:
                    if writer is not None:
                        try:
                            writer.close()
                        except Exception:
                            pass
                    reader = writer = None
                    if time.monotonic() - last_ok > self._takeover_after:
                        self._promote()
                        return
                await asyncio.sleep(self._replicate_interval)
        except asyncio.CancelledError:
            pass
        finally:
            if writer is not None:
                try:
                    writer.close()
                except Exception:
                    pass

    def _promote(self):
        """Standby → primary. The replicated state may lag the dead primary
        by up to one replicate interval, so old-epoch stream seqs can sit
        AHEAD of our counters — a fresh epoch forces every client to resume
        streams from 0 and resync through the gap protocol (indexer
        snapshot restore) instead of silently skipping rolled-back entries."""
        self.core.epoch = f"{random.getrandbits(64):016x}"
        self.is_standby = False
        logger.warning("standby promoted to primary (epoch %s)",
                       self.core.epoch)
        # fence the OLD primary: if it was merely paused/partitioned (not
        # dead) it would otherwise keep serving its connected clients
        # forever — split brain. Keep probing its address; on contact,
        # demote it into OUR standby.
        self._fence_task = asyncio.get_running_loop().create_task(
            self._fence_old_primary(self._standby_of))

    async def _fence_old_primary(self, old_addr: str):
        host, _, port = old_addr.rpartition(":")
        host, port = host or "127.0.0.1", int(port)
        while True:
            try:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection(host, port), 5.0)
                try:
                    await write_frame(writer, {"t": "req", "id": 1,
                                               "op": "demote",
                                               "port": self._port,
                                               "epoch": self.core.epoch})
                    msg = await asyncio.wait_for(read_frame(reader), 10.0)
                    if msg.get("ok"):
                        logger.warning("old primary %s demoted into standby",
                                       old_addr)
                        return
                finally:
                    try:
                        writer.close()
                    except Exception:
                        pass
            except asyncio.CancelledError:
                raise
            except Exception:
                pass
            await asyncio.sleep(max(self._replicate_interval * 2, 1.0))

    async def demote(self, new_primary: str, epoch: Optional[str] = None):
        """A newer primary exists (it fenced us): reject clients from now
        on — closing their conns makes them fail over within one reconnect
        cycle — and fall in line as the new primary's standby.

        Trust model: like every other op on this plane (any client may
        kv_delete_prefix the world), demote assumes a trusted network — the
        reference's etcd/NATS deployments carry the same assumption inside
        the cluster. Two guards bound the blast radius of a stray frame:
        the epoch must differ from ours (a real fencer always promoted
        under a fresh one), and a demotion toward a dead/bogus peer
        self-heals — the standby loop re-promotes after ``takeover_after``
        of failed pulls."""
        if self.is_standby:
            return
        if epoch is not None and epoch == self.core.epoch:
            logger.warning("ignoring demote carrying our own epoch")
            return
        logger.warning("demoted: %s took over while we were unreachable; "
                       "becoming its standby", new_primary)
        self.is_standby = True
        self._standby_of = new_primary
        for conn in list(self._conns):
            try:
                conn.writer.close()
            except Exception:
                pass
        if self._standby_task is None or self._standby_task.done():
            self._standby_task = asyncio.get_running_loop().create_task(
                self._standby_loop())

    def _write_state(self, data: bytes) -> None:
        tmp = f"{self._persist_path}.tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, self._persist_path)  # atomic: never a torn snapshot

    async def _persist_loop(self):
        try:
            while True:
                await asyncio.sleep(self._persist_interval)
                try:
                    # dump on the LOOP thread: the core's dicts are mutated
                    # by loop-thread handlers, so iterating them off-thread
                    # races ("dict changed size"); only the file IO moves
                    # to a worker
                    data = self.core.dump_state()
                    await asyncio.to_thread(self._write_state, data)
                except Exception:
                    logger.exception("state snapshot failed; retrying next tick")
        except asyncio.CancelledError:
            pass

    async def stop(self):
        if self._fence_task:
            self._fence_task.cancel()
            try:
                await self._fence_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._standby_task:
            self._standby_task.cancel()
            try:
                await self._standby_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._persist_task:
            self._persist_task.cancel()
            try:
                # an in-flight to_thread write can't be cancelled mid-write;
                # await it so it can't land AFTER (and clobber) the final
                # flush below
                await self._persist_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._persist_path:
            try:
                # final flush: clean shutdown loses nothing
                self._write_state(self.core.dump_state())
            except Exception:
                logger.exception("final state snapshot failed")
        if self._server:
            await close_server(self._server, [c.writer for c in self._conns])
        await self.core.close()

    async def _on_conn(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        conn = _ServerConn(self.core, reader, writer, server=self)
        self._conns.add(conn)
        try:
            await conn.run()
        finally:
            self._conns.discard(conn)


class _ServerConn:
    """Per-client server-side connection: dispatches ops onto the core plane."""

    def __init__(self, core: LocalControlPlane, reader, writer, server=None):
        self.core = core
        self.reader = reader
        self.writer = writer
        self.server = server
        self._wlock = asyncio.Lock()
        self._watch_tasks: dict[int, asyncio.Task] = {}
        self._watch_handles: dict[int, Watch] = {}
        self._sub_tasks: dict[int, asyncio.Task] = {}
        self._sub_handles: dict[int, object] = {}
        self._svc_cancels: dict[int, Callable] = {}
        self._pending_svc: dict[int, asyncio.Future] = {}
        self._next_rid = 0

    async def _send(self, obj):
        async with self._wlock:
            await write_frame(self.writer, obj)

    async def run(self):
        try:
            while True:
                try:
                    msg = await read_frame(self.reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                t = msg.get("t")
                if t == "req":
                    _spawn_kept(self._handle_req(msg))
                elif t == "svc_res":
                    fut = self._pending_svc.pop(msg["rid"], None)
                    if fut and not fut.done():
                        if msg.get("ok", False):
                            fut.set_result(msg.get("payload", b""))
                        else:
                            fut.set_exception(RuntimeError(msg.get("error", "remote handler error")))
        finally:
            await self._cleanup()

    async def _cleanup(self):
        for task in list(self._watch_tasks.values()) + list(self._sub_tasks.values()):
            task.cancel()
        for h in self._watch_handles.values():
            await h.cancel()
        for h in self._sub_handles.values():
            await h.cancel()  # type: ignore[attr-defined]
        for cancel in self._svc_cancels.values():
            await cancel()
        for fut in self._pending_svc.values():
            if not fut.done():
                fut.set_exception(ConnectionError("client disconnected"))
        await self.core.revoke_owned(self)
        try:
            self.writer.close()
        except Exception:
            pass

    async def _handle_req(self, msg):
        rid = msg["id"]
        op = msg["op"]
        if op == "demote" and self.server is not None:
            # fencing from a promoted standby (see _fence_old_primary);
            # its reachable address = the conn's source IP + its port
            peer = self.writer.get_extra_info("peername") or ("127.0.0.1",)
            await self._send({"t": "res", "id": rid, "ok": True,
                              "value": None})
            await self.server.demote(f"{peer[0]}:{msg['port']}",
                                     epoch=msg.get("epoch"))
            return
        # a standby mirrors state but serves no clients: reject every op so
        # a multi-address RemoteControlPlane fails over to the primary
        # (dump_state stays open — it is how replication reads us/peers)
        if (self.server is not None and self.server.is_standby
                and op != "dump_state"):
            await self._send({"t": "res", "id": rid, "ok": False,
                              "error": "standby",
                              "detail": "hub is a standby replica"})
            return
        try:
            result = await self._dispatch(op, msg)
            await self._send({"t": "res", "id": rid, "ok": True, "value": result})
        except NoRespondersError as e:
            await self._send({"t": "res", "id": rid, "ok": False, "error": "no_responders", "detail": str(e)})
        except Exception as e:
            logger.exception("control-plane op %s failed", op)
            await self._send({"t": "res", "id": rid, "ok": False, "error": "error", "detail": repr(e)})

    async def _dispatch(self, op, m):
        core = self.core
        if op == "kv_put":
            await core.kv_put(m["key"], m["value"], m.get("lease"))
        elif op == "kv_create":
            return await core.kv_create(m["key"], m["value"], m.get("lease"))
        elif op == "kv_get":
            return core._kv.get(m["key"])
        elif op == "kv_get_prefix":
            return await core.kv_get_prefix(m["prefix"])
        elif op == "kv_delete":
            return await core.kv_delete(m["key"])
        elif op == "kv_delete_prefix":
            return await core.kv_delete_prefix(m["prefix"])
        elif op == "lease_create":
            return await core.lease_create(m.get("ttl", DEFAULT_LEASE_TTL), owner=self)
        elif op == "lease_keepalive":
            return await core.lease_keepalive(m["lease"])
        elif op == "lease_revoke":
            await core.lease_revoke(m["lease"])
        elif op == "publish":
            await core.publish(m["subject"], m["payload"])
        elif op == "request":
            return await core.request(m["subject"], m["payload"], m.get("req_timeout", 30.0))
        elif op == "watch":
            return await self._start_watch(m["wid"], m["prefix"])
        elif op == "watch_cancel":
            await self._stop_watch(m["wid"])
        elif op == "subscribe":
            await self._start_sub(m["sid"], m["subject"], m.get("queue_group"))
        elif op == "sub_cancel":
            await self._stop_sub(m["sid"])
        elif op == "serve":
            await self._start_serve(m["svc_id"], m["subject"])
        elif op == "serve_cancel":
            cancel = self._svc_cancels.pop(m["svc_id"], None)
            if cancel:
                await cancel()
        elif op == "epoch":
            return core.epoch
        elif op == "hub_stats":
            return await core.hub_stats()
        elif op == "dump_state":
            return core.dump_state()
        elif op == "queue_push":
            await core.queue_push(m["queue"], m["payload"])
        elif op == "queue_pop":
            return await core.queue_pop(m["queue"], m.get("pop_timeout", 30.0))
        elif op == "queue_depth":
            return await core.queue_depth(m["queue"])
        elif op == "stream_publish":
            return await core.stream_publish(m["stream"], m["payload"])
        elif op == "stream_subscribe":
            await self._start_stream_sub(m["sid"], m["stream"], m.get("start_seq", 0))
        elif op == "stream_last_seq":
            return await core.stream_last_seq(m["stream"])
        elif op == "stream_first_seq":
            return await core.stream_first_seq(m["stream"])
        elif op == "object_put":
            await core.object_put(m["bucket"], m["name"], m["data"])
        elif op == "object_get":
            return await core.object_get(m["bucket"], m["name"])
        elif op == "object_delete":
            await core.object_delete(m["bucket"], m["name"])
        else:
            raise ValueError(f"unknown op {op}")
        return None

    async def _start_watch(self, wid, prefix):
        watch = await self.core.watch_prefix(prefix)
        self._watch_handles[wid] = watch

        async def pump():
            async for ev in watch:
                await self._send({"t": "watch_ev", "wid": wid, "ev": ev.type, "key": ev.key, "value": ev.value})

        self._watch_tasks[wid] = asyncio.get_running_loop().create_task(pump())
        return watch.snapshot

    async def _stop_watch(self, wid):
        task = self._watch_tasks.pop(wid, None)
        handle = self._watch_handles.pop(wid, None)
        if handle:
            await handle.cancel()
        if task:
            task.cancel()

    async def _start_sub(self, sid, subject, queue_group):
        sub = await self.core.subscribe(subject, queue_group)
        self._sub_handles[sid] = sub

        async def pump():
            async for subj, payload in sub:
                await self._send({"t": "sub_msg", "sid": sid, "subject": subj, "payload": payload})

        self._sub_tasks[sid] = asyncio.get_running_loop().create_task(pump())

    async def _start_stream_sub(self, sid, stream, start_seq):
        sub = await self.core.stream_subscribe(stream, start_seq)
        self._sub_handles[sid] = sub

        async def pump():
            async for seq, payload in sub:
                await self._send({"t": "stream_msg", "sid": sid, "seq": seq, "payload": payload})

        self._sub_tasks[sid] = asyncio.get_running_loop().create_task(pump())

    async def _stop_sub(self, sid):
        task = self._sub_tasks.pop(sid, None)
        handle = self._sub_handles.pop(sid, None)
        if handle:
            await handle.cancel()  # type: ignore[attr-defined]
        if task:
            task.cancel()

    async def _start_serve(self, svc_id, subject):
        async def forward(payload: bytes) -> bytes:
            self._next_rid += 1
            rid = self._next_rid
            fut = asyncio.get_running_loop().create_future()
            self._pending_svc[rid] = fut
            try:
                await self._send(
                    {"t": "svc_req", "rid": rid, "svc_id": svc_id, "subject": subject, "payload": payload}
                )
                return await fut
            finally:
                # On timeout/cancellation the caller abandons the future;
                # drop the entry so it cannot accumulate for the conn lifetime.
                self._pending_svc.pop(rid, None)

        cancel = await self.core.serve(subject, forward, owner=self)
        self._svc_cancels[svc_id] = cancel


class RemoteControlPlane(ControlPlane):
    """TCP client to a :class:`ControlPlaneServer`.

    Survives hub restarts (r1 verdict weak #8: a dropped connection used to
    permanently kill the client): on connection loss the client reconnects
    with backoff and REPLAYS its registered state — service registrations,
    prefix watches (fresh snapshots delivered as synthetic puts), pub/sub
    subscriptions, and durable-stream subscriptions resumed from the last
    seen seq. In-flight request futures fail with ControlPlaneClosed (the
    callers' retry logic owns those); higher layers re-register leases via
    ``add_reconnect_callback``.

    ``address`` may be a comma-separated list (``h1:p1,h2:p2``) naming a
    primary plus warm standbys: connect and every reconnect attempt cycle
    through the list, and a hub answering ``standby`` counts as down — so
    a standby's promotion is discovered by ordinary failover. An epoch
    change after failover resets stream cursors exactly like a hub restart.
    """

    RECONNECT_BACKOFF = (0.2, 0.5, 1.0, 2.0, 5.0)

    def __init__(self, address: str):
        self._addrs = []
        for part in address.split(","):
            part = part.strip()
            if part:
                host, _, port = part.rpartition(":")
                self._addrs.append((host or "127.0.0.1", int(port)))
        if not self._addrs:
            raise ValueError(f"no control-plane address in {address!r}")
        self._addr_i = 0  # index of the address currently/last connected
        self._host, self._port = self._addrs[0]
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._wlock = asyncio.Lock()
        self._pending: dict[int, asyncio.Future] = {}
        self._next_id = 0
        self._watch_queues: dict[int, asyncio.Queue] = {}
        self._sub_queues: dict[int, asyncio.Queue] = {}
        self._handlers: dict[int, ServiceHandler] = {}
        self._rx_task: Optional[asyncio.Task] = None
        self._closed = False
        self._connected = False
        self._established = False  # ever fully connected (epoch verified)
        # replay metadata for reconnect
        self._serve_meta: dict[int, str] = {}  # svc_id -> subject
        self._watch_meta: dict[int, str] = {}  # wid -> prefix
        self._sub_meta: dict[int, tuple] = {}  # sid -> ("sub", subject, qg) | ("stream", stream, last_seq)
        self._reconnect_task: Optional[asyncio.Task] = None
        self._reconnect_cbs: list = []

    def add_reconnect_callback(self, cb) -> None:
        """``async cb()`` invoked after each successful reconnect+replay
        (runtime uses this to re-create its lease + registrations)."""
        self._reconnect_cbs.append(cb)

    async def _open(self, i: int) -> None:
        """Dial address ``i`` and verify it serves (standbys reject the
        epoch call). On failure the half-open conn is torn down so its rx
        task cannot linger."""
        host, port = self._addrs[i]
        self._reader, self._writer = await asyncio.open_connection(host, port)
        self._connected = True
        self._rx_task = asyncio.get_running_loop().create_task(self._rx_loop())
        try:
            epoch = await self._call("epoch", timeout=10.0)
        except Exception:
            self._connected = False
            try:
                self._writer.close()
            except Exception:
                pass
            raise
        self._addr_i = i
        self._host, self._port = host, port
        self._new_epoch = epoch

    async def connect(self) -> "RemoteControlPlane":
        last_err: Optional[Exception] = None
        for off in range(len(self._addrs)):
            try:
                await self._open((self._addr_i + off) % len(self._addrs))
                self._epoch = self._new_epoch
                self._established = True
                return self
            except Exception as e:  # noqa: BLE001 — try the next address
                last_err = e
        raise last_err

    async def _rx_loop(self):
        try:
            while True:
                msg = await read_frame(self._reader)
                t = msg.get("t")
                if t == "res":
                    fut = self._pending.pop(msg["id"], None)
                    if fut and not fut.done():
                        if msg["ok"]:
                            fut.set_result(msg.get("value"))
                        elif msg.get("error") == "no_responders":
                            fut.set_exception(NoRespondersError(msg.get("detail", "")))
                        else:
                            fut.set_exception(RuntimeError(msg.get("detail", "control plane error")))
                elif t == "watch_ev":
                    q = self._watch_queues.get(msg["wid"])
                    if q:
                        q.put_nowait(WatchEvent(msg["ev"], msg["key"], msg.get("value") or b""))
                elif t == "sub_msg":
                    q = self._sub_queues.get(msg["sid"])
                    if q:
                        q.put_nowait((msg["subject"], msg["payload"]))
                elif t == "stream_msg":
                    sid = msg["sid"]
                    q = self._sub_queues.get(sid)
                    if q:
                        meta = self._sub_meta.get(sid)
                        if meta and meta[0] == "stream":
                            self._sub_meta[sid] = ("stream", meta[1], msg["seq"])
                        q.put_nowait((msg["seq"], msg["payload"]))
                elif t == "svc_req":
                    _spawn_kept(self._handle_svc(msg))
        except (asyncio.IncompleteReadError, ConnectionError, asyncio.CancelledError):
            pass
        finally:
            self._connected = False
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ControlPlaneClosed())
            self._pending.clear()
            if not self._closed and self._established:
                # guard against duplicate loops: a replay failure inside a
                # RUNNING reconnect loop also lands here when its fresh
                # rx task dies — that loop keeps retrying, don't stack one.
                # (_established gates out rx tasks of PROBE connections made
                # while connect() is still cycling the address list)
                if self._reconnect_task is None or self._reconnect_task.done():
                    logger.warning("control-plane connection lost; reconnecting")
                    self._reconnect_task = asyncio.get_running_loop().create_task(
                        self._reconnect_loop())
            else:
                for q in list(self._watch_queues.values()) + list(self._sub_queues.values()):
                    q.put_nowait(None)

    async def _reconnect_loop(self):
        attempt = 0
        while not self._closed:
            delay = self.RECONNECT_BACKOFF[
                min(attempt, len(self.RECONNECT_BACKOFF) - 1)]
            await asyncio.sleep(delay)
            attempt += 1
            try:
                # cycle the address list: the current hub first, then its
                # standbys — a promoted standby is found within one cycle
                await self._open((self._addr_i + attempt - 1)
                                 % len(self._addrs))
                await self._replay()
                logger.info("control-plane reconnected after %d attempt(s)",
                            attempt)
                for cb in list(self._reconnect_cbs):
                    try:
                        await cb()
                    except Exception:
                        logger.exception("reconnect callback failed")
                return
            except Exception:
                self._connected = False
                if self._writer is not None:
                    try:  # make sure a half-open conn's rx task dies
                        self._writer.close()
                    except Exception:
                        pass
                logger.warning("control-plane reconnect attempt %d failed",
                               attempt)

    async def _replay(self):
        """Re-establish serves, watches, and subscriptions on the new conn."""
        # epoch check: a RESTARTED hub resets stream seq counters, so seqs
        # from the previous epoch are meaningless — resume every stream from
        # 0 (comparing seqs alone cannot detect a restarted hub whose new
        # counter already passed our old high-water mark)
        epoch = await self._call("epoch")
        new_epoch = epoch != getattr(self, "_epoch", None)
        self._epoch = epoch
        if new_epoch:
            for sid, meta in list(self._sub_meta.items()):
                if meta[0] == "stream":
                    self._sub_meta[sid] = ("stream", meta[1], 0)
                    # A promoted standby CONTINUES the replicated seq
                    # numbering, so publishes the old primary took after the
                    # last replication tick are lost without any seq gap the
                    # consumer could observe — its next delivered seq is
                    # contiguous with the last one it saw. Surface the
                    # discontinuity in-band: a negative-seq marker ahead of
                    # the re-subscribed tail tells stream consumers (the KV
                    # indexers) to treat their state as suspect and resync
                    # instead of waiting for the audit cadence to notice.
                    q = self._sub_queues.get(sid)
                    if q is not None:
                        q.put_nowait((EPOCH_MARKER_SEQ,
                                      msgpack.packb({"epoch_changed": epoch})))
        for svc_id, subject in list(self._serve_meta.items()):
            await self._call("serve", svc_id=svc_id, subject=subject)
        for wid, prefix in list(self._watch_meta.items()):
            snapshot = await self._call("watch", wid=wid, prefix=prefix)
            q = self._watch_queues.get(wid)
            if q is not None:
                # deliver the fresh snapshot as synthetic puts — watch
                # consumers (discovery, clients) apply puts idempotently;
                # deletions during the outage surface as NoResponders later
                for k, v in (snapshot or {}).items():
                    q.put_nowait(WatchEvent("put", k, v or b""))
        for sid, meta in list(self._sub_meta.items()):
            if meta[0] == "sub":
                await self._call("subscribe", sid=sid, subject=meta[1],
                                 queue_group=meta[2])
            else:
                await self._call("stream_subscribe", sid=sid, stream=meta[1],
                                 start_seq=meta[2])

    async def _handle_svc(self, msg):
        handler = self._handlers.get(msg["svc_id"])
        if handler is None:
            await self._send({"t": "svc_res", "rid": msg["rid"], "ok": False, "error": "no handler"})
            return
        try:
            result = await handler(msg["payload"])
            await self._send({"t": "svc_res", "rid": msg["rid"], "ok": True, "payload": result})
        except Exception as e:
            logger.exception("service handler failed")
            await self._send({"t": "svc_res", "rid": msg["rid"], "ok": False, "error": repr(e)})

    async def _send(self, obj):
        if self._closed or not self._connected:
            raise ControlPlaneClosed()
        async with self._wlock:
            await write_frame(self._writer, obj)

    async def _call(self, op: str, timeout: float = 60.0, **kwargs):
        self._next_id += 1
        rid = self._next_id
        fut = asyncio.get_running_loop().create_future()
        self._pending[rid] = fut
        try:
            await self._send({"t": "req", "id": rid, "op": op, **kwargs})
            return await asyncio.wait_for(fut, timeout)
        finally:
            # a send failure/timeout abandons the future — drop it so a
            # later rx-loop teardown can't set an exception nobody will
            # ever retrieve (the loop's exception handler would flag it)
            self._pending.pop(rid, None)
            if fut.done() and not fut.cancelled():
                fut.exception()  # mark retrieved (timeout/send-fail races)
            else:
                fut.cancel()

    # -- KV --
    async def kv_put(self, key, value, lease_id=None):
        await self._call("kv_put", key=key, value=value, lease=lease_id)

    async def kv_create(self, key, value, lease_id=None) -> bool:
        return await self._call("kv_create", key=key, value=value, lease=lease_id)

    async def kv_get(self, key):
        return await self._call("kv_get", key=key)

    async def kv_get_prefix(self, prefix):
        return await self._call("kv_get_prefix", prefix=prefix)

    async def kv_delete(self, key):
        return await self._call("kv_delete", key=key)

    async def kv_delete_prefix(self, prefix):
        return await self._call("kv_delete_prefix", prefix=prefix)

    async def watch_prefix(self, prefix) -> Watch:
        self._next_id += 1
        wid = self._next_id
        q: asyncio.Queue = asyncio.Queue()
        self._watch_queues[wid] = q
        self._watch_meta[wid] = prefix
        snapshot = await self._call("watch", wid=wid, prefix=prefix)

        async def cancel():
            self._watch_queues.pop(wid, None)
            self._watch_meta.pop(wid, None)
            q.put_nowait(None)
            if not self._closed:
                try:
                    await self._call("watch_cancel", wid=wid)
                except ControlPlaneClosed:
                    pass

        return Watch(dict(snapshot or {}), q, cancel)

    # -- Leases --
    async def lease_create(self, ttl=DEFAULT_LEASE_TTL) -> int:
        return await self._call("lease_create", ttl=ttl)

    async def lease_keepalive(self, lease_id) -> bool:
        return await self._call("lease_keepalive", lease=lease_id)

    async def lease_revoke(self, lease_id):
        await self._call("lease_revoke", lease=lease_id)

    # -- Pub/sub --
    async def publish(self, subject, payload):
        chaos = get_chaos()
        if chaos is not None:
            await chaos.pre("plane.publish")
            if chaos.should_drop("plane.publish"):
                return  # injected loss before the hub ever sees the message
        await self._call("publish", subject=subject, payload=payload)

    async def subscribe(self, subject, queue_group=None) -> Subscription:
        self._next_id += 1
        sid = self._next_id
        q: asyncio.Queue = asyncio.Queue()
        self._sub_queues[sid] = q
        self._sub_meta[sid] = ("sub", subject, queue_group)
        await self._call("subscribe", sid=sid, subject=subject, queue_group=queue_group)

        async def cancel():
            self._sub_queues.pop(sid, None)
            self._sub_meta.pop(sid, None)
            q.put_nowait(None)
            if not self._closed:
                try:
                    await self._call("sub_cancel", sid=sid)
                except ControlPlaneClosed:
                    pass

        return Subscription(q, cancel)

    async def request(self, subject, payload, timeout=30.0) -> bytes:
        return await self._call(
            "request", timeout=timeout + 5.0, subject=subject, payload=payload, req_timeout=timeout
        )

    async def serve(self, subject, handler):
        self._next_id += 1
        svc_id = self._next_id
        self._handlers[svc_id] = handler
        self._serve_meta[svc_id] = subject
        await self._call("serve", svc_id=svc_id, subject=subject)

        async def cancel():
            self._handlers.pop(svc_id, None)
            self._serve_meta.pop(svc_id, None)
            if not self._closed:
                try:
                    await self._call("serve_cancel", svc_id=svc_id)
                except ControlPlaneClosed:
                    pass

        return cancel

    # -- Work queues --
    async def queue_push(self, queue, payload):
        await self._call("queue_push", queue=queue, payload=payload)

    async def queue_pop(self, queue, timeout: float = 30.0):
        return await self._call("queue_pop", timeout=timeout + 5.0,
                                queue=queue, pop_timeout=timeout)

    async def queue_depth(self, queue) -> int:
        return await self._call("queue_depth", queue=queue)

    # -- Streams --
    async def stream_publish(self, stream, payload) -> int:
        return await self._call("stream_publish", stream=stream, payload=payload)

    async def stream_subscribe(self, stream, start_seq=0) -> StreamSub:
        self._next_id += 1
        sid = self._next_id
        q: asyncio.Queue = asyncio.Queue()
        self._sub_queues[sid] = q
        self._sub_meta[sid] = ("stream", stream, start_seq)
        await self._call("stream_subscribe", sid=sid, stream=stream, start_seq=start_seq)

        async def cancel():
            self._sub_queues.pop(sid, None)
            self._sub_meta.pop(sid, None)
            q.put_nowait(None)
            if not self._closed:
                try:
                    await self._call("sub_cancel", sid=sid)
                except ControlPlaneClosed:
                    pass

        return StreamSub(q, cancel)

    async def stream_last_seq(self, stream) -> int:
        return await self._call("stream_last_seq", stream=stream)

    async def stream_first_seq(self, stream) -> int:
        return await self._call("stream_first_seq", stream=stream)

    async def get_epoch(self) -> str:
        return await self._call("epoch")

    async def hub_stats(self) -> dict:
        """The hub's self-instrumentation (event counters + publish
        latency) — surfaced by ``dynctl top`` and the metrics aggregator."""
        return await self._call("hub_stats")

    # -- Object store --
    async def object_put(self, bucket, name, data):
        await self._call("object_put", bucket=bucket, name=name, data=data)

    async def object_get(self, bucket, name):
        return await self._call("object_get", bucket=bucket, name=name)

    async def object_delete(self, bucket, name):
        await self._call("object_delete", bucket=bucket, name=name)

    async def close(self):
        self._closed = True
        if self._reconnect_task:
            self._reconnect_task.cancel()
        if self._rx_task:
            self._rx_task.cancel()
        if self._writer:
            try:
                self._writer.close()
            except Exception:
                pass
        for q in list(self._watch_queues.values()) + list(self._sub_queues.values()):
            q.put_nowait(None)
