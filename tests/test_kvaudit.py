"""KV index audit plane (docs/observability.md "KV audit"): worker tier
ledger digests, radix-side inline worker digests, the kv_digest wire op,
phantom/missing/dangling classification with self-healing resync,
stale-advert pull tagging + suspicion, resync idempotency under racing
live events, tombstone accounting, and hub KV-stream health."""

import asyncio
import json
import random
import time

import msgpack
import pytest

from dynamo_tpu.observability.kvaudit import (
    KV_AUDIT_SUSPECT_SUBJECT,
    AuditConfig,
    KvAuditor,
    WorkerKvLedger,
    fetch_kv_chain,
    fetch_kv_digest,
    serve_kv_digest,
)
from dynamo_tpu.router.indexer import KvIndexer, RadixTree
from dynamo_tpu.router.protocols import (
    KvCacheEvent,
    RouterEvent,
    StoredBlock,
)
from dynamo_tpu.router.publisher import KvEventPublisher, reachable_chain
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.control_plane import LocalControlPlane
from dynamo_tpu.tokens import (
    compute_block_hash_for_seq,
    compute_seq_hash_for_block,
)

pytestmark = pytest.mark.anyio

W0, W1 = 0x10, 0x20


def chain_hashes(tokens, bs=4):
    local = compute_block_hash_for_seq(tokens, bs)
    return local, compute_seq_hash_for_block(local)


def stored_blocks(local, ext):
    return [StoredBlock(e, l) for e, l in zip(ext, local)]


async def settle(check, timeout=5.0, msg="never settled"):
    for _ in range(int(timeout / 0.01)):
        if check():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(msg)


# ------------------------------------------------------------------ ledger


def test_ledger_union_and_tier_digests():
    led = WorkerKvLedger()
    led.add("g1", 3)
    led.add("g2", 3)   # second tier: union digest must not move
    led.add("g2", 11)
    led.add("g4", 7)   # owned-G4 is NOT servable: union untouched
    assert led.servable_digest() == (3 ^ 11, 2)
    assert sorted(led.servable_hashes()) == [3, 11]
    d = led.digest()
    assert d["tiers"]["g1"] == {"xor": 3, "count": 1}
    assert d["tiers"]["g2"] == {"xor": 3 ^ 11, "count": 2}
    assert d["tiers"]["g4"] == {"xor": 7, "count": 1}
    # dropping ONE of two servable copies keeps the block in the union
    led.remove("g1", 3)
    assert led.servable_digest() == (3 ^ 11, 2)
    led.remove("g2", 3)
    assert led.servable_digest() == (11, 1)
    # double-add / double-remove are digest no-ops
    led.add("g2", 11)
    led.remove("g1", 3)
    assert led.servable_digest() == (11, 1)
    led.remove_all("g2")
    assert led.servable_digest() == (0, 0)
    assert led.digest()["tiers"]["g4"]["count"] == 1  # untouched by g2 clear


def test_ledger_matches_bruteforce_over_random_ops():
    rng = random.Random(7)
    led = WorkerKvLedger()
    truth: dict[str, set] = {t: set() for t in ("g1", "g2", "g3", "g4")}
    for _ in range(3000):
        tier = rng.choice(("g1", "g2", "g3", "g4"))
        h = rng.randrange(1, 50)
        if rng.random() < 0.5:
            led.add(tier, h)
            truth[tier].add(h)
        else:
            led.remove(tier, h)
            truth[tier].discard(h)
    servable = truth["g1"] | truth["g2"] | truth["g3"]
    xor = 0
    for h in servable:
        xor ^= h
    assert led.servable_digest() == (xor, len(servable))
    assert set(led.servable_hashes()) == servable
    for t, s in truth.items():
        x = 0
        for h in s:
            x ^= h
        assert led.digest()["tiers"][t] == {"xor": x, "count": len(s)}


# ------------------------------------------------------- radix-side digests


def _tree_bruteforce(tree: RadixTree, worker: int):
    hashes = tree.worker_hashes(worker)
    x = 0
    for h in hashes:
        x ^= h & ((1 << 64) - 1)
    return x, len(hashes)


def test_radix_worker_digests_inline():
    tree = RadixTree()
    local, ext = chain_hashes(list(range(16)))
    ev = RouterEvent(W0, KvCacheEvent.stored(1, None, stored_blocks(local, ext)))
    tree.apply_event(ev)
    assert tree.worker_digest(W0) == _tree_bruteforce(tree, W0)
    assert tree.worker_counts() == {W0: 4}
    # idempotent re-store (resync replay) must NOT double-fold
    tree.apply_event(ev)
    assert tree.worker_digest(W0) == _tree_bruteforce(tree, W0)
    assert tree.worker_counts() == {W0: 4}
    # a second worker on the same chain digests independently
    tree.apply_event(RouterEvent(
        W1, KvCacheEvent.stored(2, None, stored_blocks(local[:2], ext[:2]))))
    assert tree.worker_counts() == {W0: 4, W1: 2}
    assert tree.worker_digest(W1) == _tree_bruteforce(tree, W1)
    # removal folds out; unknown-hash removal is a no-op
    tree.apply_event(RouterEvent(W0, KvCacheEvent.removed(3, ext[2:])))
    tree.apply_event(RouterEvent(W0, KvCacheEvent.removed(4, [999999])))
    assert tree.worker_digest(W0) == _tree_bruteforce(tree, W0)
    assert tree.worker_counts()[W0] == 2
    # cleared / worker death drops the whole digest
    tree.remove_worker(W0)
    assert tree.worker_digest(W0) == (0, 0)
    assert W0 not in tree.worker_counts()
    assert tree.worker_digest(W1) == _tree_bruteforce(tree, W1)


def test_radix_digest_survives_dump_load():
    tree = RadixTree()
    local, ext = chain_hashes(list(range(24)))
    tree.apply_event(RouterEvent(
        W0, KvCacheEvent.stored(1, None, stored_blocks(local, ext))))
    tree.apply_event(RouterEvent(
        W1, KvCacheEvent.stored(2, None, stored_blocks(local[:3], ext[:3]))))
    restored = RadixTree.load(tree.dump())
    for w in (W0, W1):
        assert restored.worker_digest(w) == tree.worker_digest(w)
    assert restored.worker_counts() == tree.worker_counts()


# ----------------------------------------------------------- kv_digest wire


async def test_digest_wire_serve_and_fetch():
    rt = await DistributedRuntime.create()
    try:
        lease = await rt.primary_lease()
        led = WorkerKvLedger()
        pub = KvEventPublisher(rt.plane, worker_id=lease, kv_block_size=4,
                               ledger=led)
        local, ext = chain_hashes(list(range(12)))
        for h in ext:
            led.add("g1", h)
        await pub.publish_stored(None, stored_blocks(local, ext))
        handle = await serve_kv_digest(rt, led, lease, publisher=pub)
        d = await fetch_kv_digest(rt.plane, lease)
        assert d["servable"]["count"] == 3
        assert d["servable"]["xor"] == led.servable_digest()[0]
        ch = await fetch_kv_chain(rt.plane, lease)
        assert set(ch["resident"]) == set(ext)
        assert ch["anchored"] == list(ext)  # parents-first order
        # a ledger-resident block the mirror never saw is NOT anchored
        led.add("g2", 424242)
        ch = await fetch_kv_chain(rt.plane, lease)
        assert 424242 in set(ch["resident"])
        assert 424242 not in set(ch["anchored"])
        await handle.stop()
        assert await fetch_kv_digest(rt.plane, lease) is None
    finally:
        await rt.shutdown()


def test_reachable_chain_membership_filter():
    # c is a child of b; with b non-resident, c must not anchor
    entries = {1: (None, 101), 2: (1, 102), 3: (2, 103)}
    full = [h for h, _p, _t in reachable_chain(dict(entries))]
    assert full == [1, 2, 3]
    part = [h for h, _p, _t in reachable_chain(dict(entries), member={1, 3})]
    assert part == [1]
    # re-inserted parent behind its children still resolves (fixpoint)
    reordered = {3: (2, 103), 2: (1, 102), 1: (None, 101)}
    assert [h for h, _p, _t in reachable_chain(reordered)] == [1, 2, 3]


# --------------------------------------------- auditor: detect/classify/heal


class _Harness:
    """One worker (ledger + publisher + digest endpoint) and one event-fed
    indexer over a shared in-process runtime."""

    def __init__(self, rt, lease, led, pub, idx, handle):
        self.rt, self.lease = rt, lease
        self.ledger, self.pub, self.idx = led, pub, idx
        self.handle = handle

    @classmethod
    async def create(cls):
        rt = await DistributedRuntime.create()
        lease = await rt.primary_lease()
        led = WorkerKvLedger()
        pub = KvEventPublisher(rt.plane, worker_id=lease, kv_block_size=4,
                               ledger=led)
        await pub.start_resync_responder()
        idx = await KvIndexer(rt.plane, kv_block_size=4).start()
        handle = await serve_kv_digest(rt, led, lease, publisher=pub)
        return cls(rt, lease, led, pub, idx, handle)

    def auditor(self, **kw):
        kw.setdefault("interval_s", 60.0)  # loop never fires; audit_once()
        kw.setdefault("settle_s", 0.01)
        return KvAuditor(self.rt.plane, self.idx, AuditConfig(**kw))

    async def announce(self, tokens):
        local, ext = chain_hashes(tokens)
        for h in ext:
            self.ledger.add("g1", h)
        await self.pub.publish_stored(None, stored_blocks(local, ext))
        await settle(lambda: self.idx.tree.worker_counts()
                     .get(self.lease, 0) >= len(ext),
                     msg="radix never learned the chain")
        return local, ext

    async def close(self):
        await self.handle.stop()
        await self.idx.stop()
        await self.pub.stop()
        await self.rt.shutdown()


async def test_audit_clean_fleet_reports_no_divergence():
    h = await _Harness.create()
    try:
        await h.announce(list(range(16)))
        aud = h.auditor()
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["phantom"] == w["missing"] == w["dangling"] == 0
        assert w["advertised_blocks"] == 4 and w["resident_blocks"] == 4
        assert aud.heals_total == {}
        # status doc landed on the plane for dynctl kv (per-replica key:
        # one auditor's stop must never blank its siblings' docs)
        docs = await h.rt.plane.kv_get_prefix(
            f"public/kvaudit/kv_events/{aud.replica_hex}")
        assert docs and all(b"workers" in v for v in docs.values())
    finally:
        await h.close()


async def test_audit_detects_phantom_and_heals():
    """A removal event lost in transit (chaos at the hub's stream append
    — no seq assigned, no gap to see): the radix keeps advertising KV the
    worker evicted. The audit must detect within one cycle, classify the
    tail as phantom, and heal via purge + ledger-aware resync."""
    from dynamo_tpu.runtime.chaos import configure_chaos

    h = await _Harness.create()
    try:
        local, ext = await h.announce(list(range(16)))
        # the eviction happens (ledger + mirror updated), its event drops
        configure_chaos("plane.publish:drop=1.0")
        try:
            for gone in ext[2:]:
                h.ledger.remove("g1", gone)
            await h.pub.publish_removed(list(ext[2:]))
        finally:
            configure_chaos(None)
        assert h.idx.tree.worker_counts()[h.lease] == 4  # still lied-to
        aud = h.auditor()
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["phantom"] == 2 and w["missing"] == 0
        assert set(w["samples"]["phantom"]) == {e & ((1 << 64) - 1)
                                                for e in ext[2:]}
        assert aud.heals_total == {"phantom": 1}
        # the heal (purge + resync replay) converges: radix == residency
        await settle(lambda: h.idx.tree.worker_counts()
                     .get(h.lease, 0) == 2, msg="resync never healed")
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["phantom"] == w["missing"] == 0
        assert w["divergence_age_s"] == 0.0
        assert w["last_heal_s_ago"] is not None
        assert aud.heals_total == {"phantom": 1}  # no re-heal once clean
    finally:
        await h.close()


async def test_audit_detects_missing_and_heals():
    """Stored events lost in transit: the worker holds (and announced,
    per its mirror) KV the radix never learned — lost reuse. Resync's
    idempotent upserts restore it without purging anything."""
    from dynamo_tpu.runtime.chaos import configure_chaos

    h = await _Harness.create()
    try:
        local, ext = chain_hashes(list(range(16)))
        for hh in ext:
            h.ledger.add("g1", hh)
        await h.pub.publish_stored(None, stored_blocks(local[:2], ext[:2]))
        await settle(lambda: h.idx.tree.worker_counts()
                     .get(h.lease, 0) == 2, msg="head never indexed")
        configure_chaos("plane.publish:drop=1.0")
        try:
            await h.pub.publish_stored(ext[1],
                                       stored_blocks(local[2:], ext[2:]))
        finally:
            configure_chaos(None)
        aud = h.auditor()
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["missing"] == 2 and w["phantom"] == 0
        assert aud.heals_total == {"missing": 1}
        await settle(lambda: h.idx.tree.worker_counts()
                     .get(h.lease, 0) == 4, msg="resync never restored")
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["missing"] == 0 and aud.heals_total == {"missing": 1}
    finally:
        await h.close()


async def test_dangling_reported_but_not_rehealed():
    """A resident block the mirror cannot re-announce (never announced —
    a store-suppression bug): no resync can restore it, so the auditor
    reports it as dangling ONCE and stops re-healing until either
    digest moves (no resync-request livelock)."""
    h = await _Harness.create()
    try:
        await h.announce(list(range(8)))
        h.ledger.add("g2", 777777)  # resident, never announced
        aud = h.auditor()
        before = h.idx.resyncs_requested
        doc = await aud.audit_once()
        w = doc["workers"][f"{h.lease:x}"]
        assert w["dangling"] == 1 and w["phantom"] == w["missing"] == 0
        assert aud.heals_total == {}
        assert h.idx.resyncs_requested == before  # nothing to resync
        st = aud.worker_state[h.lease]
        assert st["skip_pair"] is not None
        # second cycle: the known pair short-circuits (no diff, no heal)
        await aud.audit_once()
        assert aud.heals_total == {}
    finally:
        await h.close()


async def test_truncated_chain_never_mass_purges(monkeypatch):
    """A worker over the MAX_CHAIN_HASHES cap serves a truncated chain
    view: phantom classification against it would mass-classify every
    advert beyond the cap and purge the worker's whole projection each
    cycle — the auditor must skip phantom/dangling on a truncated view
    and never purge."""
    import dynamo_tpu.observability.kvaudit as ka

    h = await _Harness.create()
    try:
        _, ext = await h.announce(list(range(32)))  # 8 blocks
        monkeypatch.setattr(ka, "MAX_CHAIN_HASHES", 4)
        h.ledger.remove("g1", ext[-1])  # real divergence (lost removal)
        aud = h.auditor()
        await aud.audit_once()
        assert h.idx.tree.worker_counts().get(h.lease, 0) == len(ext)
        assert aud.heals_total == {}
    finally:
        await h.close()


async def test_departed_worker_tombstone_leak_purged():
    """A worker that died BEFORE this replica was born never sends it a
    delete event, yet the hub ring replays its stored events into the
    newborn radix — a permanent phantom no resync can retract (the
    corpse's resync responder died with it). With a liveness oracle the
    auditor purges it after two endpoint-less sightings (one cycle of
    watch-lag grace); a live pre-audit worker is never purged."""
    h = await _Harness.create()
    try:
        _, ext = await h.announce(list(range(8)))
        aud = h.auditor()
        # worker dies: digest discovery key gone, instance lease lapsed
        await h.handle.stop()
        aud.alive_fn = lambda: set()
        await aud.audit_once()  # sighting 1: watch-lag grace
        assert h.idx.tree.worker_counts().get(h.lease, 0) == len(ext)
        assert aud.heals_total == {}
        doc = await aud.audit_once()  # sighting 2: purge
        assert h.idx.tree.worker_counts().get(h.lease, 0) == 0
        assert aud.heals_total == {"departed": 1}
        w = doc["workers"][f"{h.lease:x}"]
        assert w["phantom"] == len(ext) and w["last_heal_s_ago"] is not None
        aud.stale_adverts[h.lease] = 3  # history for the corpse
        # next cycle sweeps state AND stale-advert history (gone from
        # both views — lease ids never recur, the dict must not grow)
        await aud.audit_once()
        assert h.lease not in aud.worker_state
        assert h.lease not in aud.stale_adverts
    finally:
        await h.close()


async def test_live_digestless_worker_never_purged():
    """No digest endpoint but still alive = a pre-audit build (or
    caching-off adverts) — informational only, never purged. Liveness
    is the FLEET-wide instance scan (kv_events is fleet-global, so a
    model-scoped view would read another model's live worker as a
    corpse); a failed scan means unknown, which never purges either."""
    h = await _Harness.create()
    try:
        _, ext = await h.announce(list(range(8)))
        aud = h.auditor()
        await h.handle.stop()  # no digest op...
        # ...but SOME serving endpoint (any model/component) still
        # registers the lease fleet-wide
        ikey = f"instances/other/backend/generate:{h.lease:x}"
        await h.rt.plane.kv_put(ikey, b"x", lease_id=h.lease)
        for _ in range(3):
            await aud.audit_once()
        assert h.idx.tree.worker_counts().get(h.lease, 0) == len(ext)
        assert aud.heals_total == {}
        # discovery scan failure = unknown liveness: stay conservative
        await h.rt.plane.kv_delete(ikey)
        orig = h.rt.plane.kv_get_prefix

        async def boom(prefix):
            raise RuntimeError("plane down")

        h.rt.plane.kv_get_prefix = boom
        try:
            for _ in range(3):
                await aud.audit_once()
        finally:
            h.rt.plane.kv_get_prefix = orig
        assert h.idx.tree.worker_counts().get(h.lease, 0) == len(ext)
        assert aud.heals_total == {}
    finally:
        await h.close()


async def test_suspicion_wakes_audits_and_decays():
    h = await _Harness.create()
    try:
        aud = h.auditor()
        await aud.start()
        await h.rt.plane.publish(
            KV_AUDIT_SUSPECT_SUBJECT,
            msgpack.packb({"worker_id": h.lease,
                           "cause": "stale_advert"}))
        # the suspect report (weight 1.0) wakes the 60s-interval loop
        # IMMEDIATELY: exactly one background cycle runs and decays the
        # suspicion — observe the monotonic signals (stale-advert count,
        # cycle count), not the transient pre-decay weight
        await settle(lambda: aud.stale_adverts.get(h.lease, 0) == 1,
                     msg="suspicion never arrived")
        await settle(lambda: aud.cycles == 1,
                     msg="suspicion never woke the audit loop")
        assert aud.suspicion.get(h.lease, 0.0) == 0.5  # 1.0 decayed once
        await aud.audit_once()
        assert aud.suspicion.get(h.lease, 0.0) == 0.25
        for _ in range(2):  # 0.25 → 0.125 → 0.0625 < 0.1 floor
            await aud.audit_once()
        assert h.lease not in aud.suspicion  # fully decayed
        assert aud.stale_adverts[h.lease] == 1  # the count is history
        from dynamo_tpu.observability.kvaudit import KV_AUDIT_STATUS_KEY

        key = KV_AUDIT_STATUS_KEY.format(stream=h.idx.stream,
                                         replica=aud.replica_hex)
        assert await h.rt.plane.kv_get(key) is not None  # cycles published
        # a crashed sibling's doc (lease-less, ts long past) is GC'd by
        # the next live cycle; a FRESH sibling doc is left alone
        stale = json.dumps({"ts": 1.0, "interval_s": 0.1}).encode()
        await h.rt.plane.kv_put("public/kvaudit/kv_events/deadbeef", stale)
        fresh_doc = json.dumps({"ts": time.time(),
                                "interval_s": 60.0}).encode()
        await h.rt.plane.kv_put("public/kvaudit/kv_events/cafe01", fresh_doc)
        await aud.audit_once()
        assert await h.rt.plane.kv_get(
            "public/kvaudit/kv_events/deadbeef") is None
        assert await h.rt.plane.kv_get(
            "public/kvaudit/kv_events/cafe01") is not None
        await h.rt.plane.kv_delete("public/kvaudit/kv_events/cafe01")
        await aud.stop()
        # stop() retracts the status doc: dynctl kv must never render a
        # dead fleet's audit state as live
        assert await h.rt.plane.kv_get(key) is None
    finally:
        await h.close()


# ------------------------------------------- ledger-aware resync retraction


async def test_resync_retracts_suppressed_removals():
    """The resync replay reconciles mirror vs ledger: an eviction whose
    removal was never even PUBLISHED (suppression bug — the mirror still
    carries the block) is retracted with a removed event, so replicas
    that did not purge heal too."""
    h = await _Harness.create()
    try:
        local, ext = await h.announce(list(range(16)))
        # suppression bug: the block leaves the tier, nobody publishes
        h.ledger.remove("g1", ext[3])
        assert ext[3] in h.pub.announced_chain()  # mirror still lies
        await h.idx._request_resync()
        await settle(lambda: h.pub.resyncs_served >= 1,
                     msg="resync never served")
        await settle(lambda: h.idx.tree.worker_counts()
                     .get(h.lease, 0) == 3, msg="retraction never landed")
        assert ext[3] not in h.pub.announced_chain()  # mirror reconciled
    finally:
        await h.close()


# ------------------------------------ resync idempotency (property test)


async def _drive_ops(plane, pub, ledger, ops, replay_at=None):
    """Apply stored/removed ops in order, firing a full resync replay
    between ops at ``replay_at`` (simulating a replay racing fresh
    events; the publisher lock makes each replay atomic on the stream,
    which is exactly the property under test)."""
    for i, (kind, parent, blocks) in enumerate(ops):
        if replay_at is not None and i == replay_at:
            await pub._replay_announced()
        if kind == "store":
            for b in blocks:
                ledger.add("g1", b.block_hash)
            await pub.publish_stored(parent, blocks)
        else:
            for bh in blocks:
                ledger.remove("g1", bh)
            await pub.publish_removed(blocks)
    if replay_at is not None and replay_at >= len(ops):
        await pub._replay_announced()


def _make_ops(rng):
    """A few chains stored block-by-block with interleaved removals."""
    ops = []
    chains = []
    for c in range(3):
        toks = [rng.randrange(1, 1000) for _ in range(16)]
        local, ext = chain_hashes(toks)
        chains.append((local, ext))
        parent = None
        for l, e in zip(local, ext):
            ops.append(("store", parent, [StoredBlock(e, l)]))
            parent = e
    # remove a few mid/tail blocks across chains
    for c, pos in ((0, 3), (1, 1), (2, 2)):
        local, ext = chains[c]
        ops.append(("remove", None, list(ext[pos:])))
    rng.shuffle(ops)
    return ops


def _canon(tree: RadixTree):
    """Canonical radix content: the (worker, hash) membership plus each
    entry's path (structure), enough to prove two trees identical."""
    d = tree.dump_obj()
    return (sorted((tuple(e[0]), tuple(e[1])) for e in d["entries"]),
            sorted((w, h, tuple(p)) for w, h, p in d["lookup"]))


async def test_resync_idempotent_under_racing_live_events():
    """Satellite (ISSUE 15): a resync replay racing fresh stored/removed
    events must converge to the same radix as a clean replay, over
    shuffled interleavings and replay positions."""
    for seed in range(6):
        rng = random.Random(seed)
        ops = _make_ops(rng)
        replay_at = rng.randrange(0, len(ops) + 1)
        plane = LocalControlPlane()
        led = WorkerKvLedger()
        pub = KvEventPublisher(plane, worker_id=W0, kv_block_size=4,
                               ledger=led)
        idx = await KvIndexer(plane, kv_block_size=4).start()
        await _drive_ops(plane, pub, led, ops, replay_at=replay_at)
        # final replay (the heal): stream's last word == mirror == ledger
        await pub._replay_announced()
        target = await plane.stream_last_seq("kv_events")
        await settle(lambda: idx._last_seq >= target,
                     msg="indexer never caught up")
        raced = _canon(idx.tree)
        await idx.stop()

        # clean reference: a fresh indexer fed ONLY a replay of the final
        # mirror state
        plane2 = LocalControlPlane()
        pub2 = KvEventPublisher(plane2, worker_id=W0, kv_block_size=4,
                                ledger=led)
        pub2._announced = dict(pub._announced)
        idx2 = await KvIndexer(plane2, kv_block_size=4).start()
        await pub2._replay_announced()
        target2 = await plane2.stream_last_seq("kv_events")
        await settle(lambda: idx2._last_seq >= target2,
                     msg="reference indexer never caught up")
        clean = _canon(idx2.tree)
        await idx2.stop()
        await plane.close()
        await plane2.close()
        assert raced == clean, f"divergence at seed {seed}"
        assert idx.tree.worker_digest(W0) == idx2.tree.worker_digest(W0)


# ------------------------------------------------- stale-advert pull outcome


class _EmptyPullClient:
    """kv_pull client whose source serves NOTHING (stale advert)."""

    def __init__(self):
        self.calls = 0

    def instance(self, _wid):
        return object()

    async def generate(self, request, mode=None, instance_id=None):
        self.calls += 1

        class _Stream:
            def __aiter__(self):
                return self

            async def __anext__(self):
                raise StopAsyncIteration

            async def cancel(self):
                pass

        return _Stream()


class _StubEngine:
    class args:
        block_size = 4

    def attach_restored(self, probe, start, blocks):
        return 0


async def test_stale_advert_pull_tagged_and_reported():
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
    from dynamo_tpu.disagg.transfer import RestoreConfig
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    plane = LocalControlPlane()
    sub = await plane.subscribe(KV_AUDIT_SUSPECT_SUBJECT)
    metrics = MetricsRegistry()
    client = _EmptyPullClient()
    handler = DecodeWorkerHandler(
        _StubEngine(), metrics=metrics, pull_clients=[client], plane=plane)
    info = {"pulls": 0, "pull_failures": 0, "restored_blocks": 0,
            "reason": None}
    covered = await handler._pull_from_sources(
        probe=None, hashes=[11, 22, 33], sources=[(W1, 3, 1.0)],
        covered=0, want=3, cfg=RestoreConfig(), ctx=None, info=info)
    assert covered == 0
    assert info["stale_adverts"] == 1 and info["pull_failures"] == 1
    assert handler._pull_outcomes._values.get(
        (("outcome", "stale_advert"),)) == 1
    # the suspicion report reached the audit subject, naming the source
    subject, payload = await asyncio.wait_for(sub._queue.get(), 2.0)
    m = msgpack.unpackb(payload, raw=False)
    assert m == {"worker_id": W1, "cause": "stale_advert"}
    await sub.cancel()
    await plane.close()


# -------------------------------------------------- tombstones + hub health


async def test_worker_monitor_counts_tombstoned_metrics():
    from dynamo_tpu.router.protocols import (ForwardPassMetrics,
                                             KV_METRICS_SUBJECT, KvStats)
    from dynamo_tpu.runtime.worker_monitor import WorkerMonitor

    plane = LocalControlPlane()
    mon = await WorkerMonitor(plane=plane).start()
    try:
        mon.purge(W0)

        async def late_publish():
            wire = {"worker_id": W0,
                    "metrics": ForwardPassMetrics(
                        kv_stats=KvStats(kv_active_blocks=9)).to_wire()}
            await plane.publish(KV_METRICS_SUBJECT, msgpack.packb(wire))

        await late_publish()
        await late_publish()
        await settle(lambda: mon.tombstoned_total == 2,
                     msg="tombstone counter never moved")
        assert W0 not in mon.load_states  # the late report stayed out
    finally:
        await mon.stop()
        await plane.close()


async def test_hub_stream_health_in_stats():
    plane = LocalControlPlane(stream_max_len=4)
    for i in range(7):
        await plane.stream_publish("kv_events", b"x%d" % i)
    await plane.publish("kv_resync.kv_events", b"resync")
    stats = await plane.hub_stats()
    kv = stats["streams"]["kv_events"]
    assert kv["last_seq"] == 7
    assert kv["first_seq"] == 4  # ring keeps the newest 4
    assert kv["truncated"] == 3
    assert stats["resyncs_requested"] == 1
    await plane.close()


def test_departed_worker_series_decay_then_drop():
    """Label-churn hygiene: a departed worker's gauge gets exactly ONE
    0-valued scrape, then the series leaves /metrics entirely — under
    autoscaler churn every restart mints a new lease hex, so 0-valued
    tombstone series must not accumulate without bound."""
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    reg = MetricsRegistry()
    g = reg.gauge("radix_blocks", "test")
    exported: dict = {}

    def scrape(workers: dict):
        HttpService._decay_departed(
            g, exported, set(workers),
            lambda whex: {"model": "m", "worker": whex})
        for whex, n in workers.items():
            g.set(n, model="m", worker=whex)
        return reg.render()

    text = scrape({"aa": 5, "bb": 3})
    assert 'worker="aa"} 5' in text and 'worker="bb"} 3' in text
    # bb departs: one decayed-to-0 scrape...
    text = scrape({"aa": 7})
    assert 'worker="bb"} 0' in text
    # ...then the series is gone, and the bookkeeping dict shed the key
    text = scrape({"aa": 7})
    assert 'worker="bb"' not in text
    assert exported == {"aa": False}
    # a returning worker re-exports cleanly
    text = scrape({"aa": 7, "bb": 1})
    assert 'worker="bb"} 1' in text


# ----------------------------------------------- frontend + mocker fleet e2e


async def test_kv_audit_http_route_and_radix_metrics():
    """End-to-end over a mocker fleet: run_mocker serves kv_digest, the
    kv-mode router starts an auditor, /v1/kv/audit answers, and /metrics
    exposes the radix shape + audit families."""
    import os

    import aiohttp

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.mocker.engine import MockEngineArgs
    from dynamo_tpu.mocker.main import run_mocker

    rt = await DistributedRuntime.create()
    engines, handles = [], []
    watcher = service = None
    os.environ["DYN_KV_AUDIT_INTERVAL"] = "0.3"
    try:
        args = MockEngineArgs(vocab_size=make_test_tokenizer().vocab_size,
                              block_size=4, num_gpu_blocks=128,
                              speedup_ratio=20.0)
        engines, handles = await run_mocker(rt, "kvaudit-e2e", args)
        manager = ModelManager()
        watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
        service = HttpService(manager, port=0, runtime=rt)
        await service.start()
        await settle(lambda: manager.list_models(), timeout=10.0,
                     msg="model never appeared")
        sm = manager.get("kvaudit-e2e")
        assert sm.router.auditor is not None

        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as http:
            async with http.post(
                    f"{base}/v1/completions",
                    json={"model": "kvaudit-e2e",
                          "prompt": "hello tokens stream from the fleet",
                          "max_tokens": 8, "stream": True,
                          "ignore_eos": True}) as resp:
                assert resp.status == 200, await resp.text()
                async for _ in resp.content:
                    pass
            # blocks were stored + announced; run one audit cycle and
            # assert a clean verdict through the HTTP surface
            await settle(lambda: sum(
                sm.router.indexer.tree.worker_counts().values()) > 0,
                msg="radix never populated")
            doc = await sm.router.auditor.audit_once()
            assert doc["workers"], doc
            assert all(w["phantom"] == 0 and w["missing"] == 0
                       for w in doc["workers"].values()), doc
            async with http.get(f"{base}/v1/kv/audit") as resp:
                assert resp.status == 200
                body = await resp.json()
            assert "kvaudit-e2e" in body["models"]
            assert body["models"]["kvaudit-e2e"]["workers"]
            async with http.get(f"{base}/metrics") as resp:
                text = await resp.text()
            for series in ("dynamo_radix_blocks", "dynamo_radix_workers",
                           "dynamo_radix_g4_blocks",
                           "dynamo_kv_audit_cycles_total"):
                assert series in text, series
            # heals counter stays MONOTONIC across model teardown: the
            # departed auditor's counts fold into a retained baseline
            # instead of vanishing from the live sum (a decreasing
            # counter reads as a process restart to rate())
            sm.router.auditor.heals_total["phantom"] = 7
            async with http.get(f"{base}/metrics") as resp:
                text = await resp.text()
            assert 'dynamo_kv_audit_heals_total{cause="phantom"} 7' in text
            gone = manager.models.pop("kvaudit-e2e")
            try:
                async with http.get(f"{base}/metrics") as resp:
                    text = await resp.text()
                assert ('dynamo_kv_audit_heals_total{cause="phantom"} 7'
                        in text)
            finally:
                manager.models["kvaudit-e2e"] = gone
    finally:
        os.environ.pop("DYN_KV_AUDIT_INTERVAL", None)
        if service is not None:
            await service.stop()
        if watcher is not None:
            await watcher.stop()
        for h in handles:
            await h.stop(graceful=False)
        for e in engines:
            await e.stop()
        await rt.shutdown()


async def test_audit_is_pure_observation_on_a_fleet(monkeypatch):
    """The same seeded prompts through a 2-rank mocker fleet with
    ``DYN_KV_AUDIT=0`` and with an auditor cycling during the wave read
    back identical texts, and the audited clean fleet shows no
    divergence."""
    import aiohttp
    import numpy as np

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.mocker.engine import MockEngineArgs
    from dynamo_tpu.mocker.main import run_mocker

    rng = np.random.default_rng(77)
    prompts = [rng.integers(10, 200, 24).tolist() for _ in range(8)]

    async def wave(name, audited: bool):
        rt = await DistributedRuntime.create()
        engines, handles = await run_mocker(rt, name, MockEngineArgs(
            vocab_size=make_test_tokenizer().vocab_size, block_size=4,
            num_gpu_blocks=72, dp_size=2, speedup_ratio=50.0))
        manager = ModelManager()
        watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
        service = HttpService(manager, port=0, runtime=rt)
        await service.start()
        auditor = None
        try:
            await settle(lambda: manager.list_models(), timeout=10.0,
                         msg="model never appeared")
            if audited:
                auditor = await KvAuditor(
                    rt.plane, manager.get(name).router.indexer,
                    AuditConfig(interval_s=0.2, settle_s=0.05)).start()
            url = f"http://127.0.0.1:{service.port}/v1/completions"
            texts = []
            async with aiohttp.ClientSession() as http:
                for p in prompts:
                    async with http.post(url, json={
                            "model": name, "prompt": p, "max_tokens": 12,
                            "ignore_eos": True}) as r:
                        assert r.status == 200, await r.text()
                        texts.append((await r.json())["choices"][0]["text"])
            if audited:
                doc = await auditor.audit_once()
                assert doc["workers"] and all(
                    w["phantom"] == 0 and w["missing"] == 0
                    for w in doc["workers"].values()), doc
            return texts
        finally:
            if auditor is not None:
                await auditor.stop()
            await service.stop()
            await watcher.stop()
            for h in handles:
                await h.stop(graceful=False)
            for e in engines:
                await e.stop()
            await rt.shutdown()

    monkeypatch.setenv("DYN_KV_AUDIT", "0")
    assert (await wave("kvaudit-on", audited=True)
            == await wave("kvaudit-off", audited=False))


async def test_mocker_ledger_parity():
    """The mocker's ledger mirrors its KvCacheSim membership exactly."""
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineArgs
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)
    from dynamo_tpu.runtime.context import Context

    eng = await MockEngine(MockEngineArgs(
        block_size=4, num_gpu_blocks=64, speedup_ratio=50.0)).start()
    try:
        req = PreprocessedRequest(
            model="m", token_ids=list(range(1, 18)),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[2])
        async for _ in eng.generate(req, Context()):
            pass
        member = set(eng.cache.active) | set(eng.cache.inactive)
        assert set(eng.kv_ledger.servable_hashes()) == member
        x = 0
        for h in member:
            x ^= h & ((1 << 64) - 1)
        assert eng.kv_ledger.servable_digest() == (x, len(member))
    finally:
        await eng.stop()
