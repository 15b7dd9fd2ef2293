"""KVBM: tier LRU/cascade behavior and offload→clear→onboard determinism.

Mirrors the reference's determinism suite (ref: tests/kvbm/
test_determinism.py:577-919 — same prompts with/without offload + cache
reset must produce identical outputs).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.kvbm import DiskTier, HostTier, KvbmManager
from dynamo_tpu.protocols import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


def page(i, nbytes=256):
    return np.full((nbytes // 4,), i, np.float32)


def test_host_tier_lru_and_budget():
    t = HostTier(capacity_bytes=4 * 512)  # fits 4 (k,v) pairs of 256B each
    for i in range(4):
        assert t.put(i, page(i), page(i)) == []
    assert len(t) == 4
    t.get(0)  # refresh 0
    ev = t.put(9, page(9), page(9))
    assert [e[0] for e in ev] == [1]  # LRU (not 0) cascades out
    assert 0 in t and 9 in t and 1 not in t


def test_disk_tier_roundtrip(tmp_path):
    t = DiskTier(str(tmp_path), capacity_bytes=3 * 512)
    for i in range(5):
        t.put(i, page(i), page(i))
    assert len(t) == 3  # budget evicted the two oldest
    assert 0 not in t and 1 not in t
    k, v = t.get(4)
    np.testing.assert_array_equal(k, page(4))


def test_manager_cascade_and_promote(tmp_path):
    m = KvbmManager(host_bytes=2 * 512, disk_dir=str(tmp_path),
                    disk_bytes=16 * 512)
    for i in range(5):
        m.put(i, page(i), page(i))
    # 3 oldest cascaded to disk, 2 newest on host
    assert len(m.host) == 2 and len(m.disk) == 3
    assert m.match_prefix([0, 1, 2, 3, 4]) == 5
    k, _ = m.get(0)  # disk hit → promoted back to host
    np.testing.assert_array_equal(k, page(0))
    assert 0 in m.host


def make_engine(**kw) -> AsyncJaxEngine:
    cfg = ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=64, max_num_seqs=8,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64),
                    decode_batch_buckets=(1, 2, 4, 8))
    defaults.update(kw)
    return AsyncJaxEngine(cfg, EngineArgs(**defaults))


def req(tokens, max_tokens=8) -> PreprocessedRequest:
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(),
    )


async def collect(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
    return toks


async def test_offload_clear_onboard_determinism():
    """Prompt served → device prefix cache cleared → same prompt again must
    onboard from the host tier and produce identical tokens."""
    prompt = list(range(1, 30))

    ref_eng = make_engine()
    want = await collect(ref_eng, req(prompt))
    await ref_eng.close()

    eng = make_engine(kvbm_host_bytes=64 << 20)
    got1 = await collect(eng, req(prompt))
    assert got1 == want
    # let async offloads drain
    for _ in range(50):
        if eng.kvbm.offloaded_blocks >= len(prompt) // 4:
            break
        await asyncio.sleep(0.02)
    assert eng.kvbm.offloaded_blocks > 0

    eng.pool.clear()  # admin clear: device prefix cache gone, tiers remain
    got2 = await collect(eng, req(prompt))
    assert got2 == want
    assert eng.kvbm.onboarded_blocks > 0  # prefix came back from G2
    assert eng.scheduler.prefix_hit_tokens > 0
    await eng.close()


async def test_onboard_from_disk_after_host_pressure(tmp_path):
    """Host tier too small to hold the prefix → blocks cascade to disk and
    still onboard correctly."""
    prompt = list(range(1, 30))
    ref_eng = make_engine()
    want = await collect(ref_eng, req(prompt))
    await ref_eng.close()

    cfg = ModelConfig.tiny()
    # one tiny block is L*bs*KV*hd*4B*2 — size host tier to ~2 blocks
    blk_bytes = 2 * cfg.num_layers * 4 * cfg.num_kv_heads * (
        cfg.hidden_size // cfg.num_heads) * 4
    eng = make_engine(kvbm_host_bytes=2 * blk_bytes,
                      kvbm_disk_dir=str(tmp_path),
                      kvbm_disk_bytes=64 << 20)
    got1 = await collect(eng, req(prompt))
    assert got1 == want
    for _ in range(50):
        if len(eng.kvbm.disk) > 0:
            break
        await asyncio.sleep(0.02)
    assert len(eng.kvbm.disk) > 0

    eng.pool.clear()
    # disk-resident prefix: the first admission does NOT block on np.load —
    # it schedules a G3→G2 promotion and recomputes. Outputs stay correct.
    got2 = await collect(eng, req(prompt))
    assert got2 == want
    # once promotion lands the prefix on host, the next cleared-cache
    # admission onboards it synchronously. Wait for the promotion task
    # itself: the two-block host tier is full at every moment, so its size
    # says nothing about WHICH blocks it holds
    await asyncio.gather(*list(eng._offload_tasks))
    eng.pool.clear()
    got3 = await collect(eng, req(prompt))
    assert got3 == want
    assert eng.kvbm.onboarded_blocks > 0
    await eng.close()


class _FakeG4Client:
    """Dict-backed G4 client with call counting (unit tests)."""

    def __init__(self):
        self.store: dict = {}
        self.puts = self.gets = self.deletes = 0

    def put(self, h, data):
        self.puts += 1
        self.store[h] = data

    def get(self, h):
        self.gets += 1
        return self.store.get(h)

    def delete(self, h):
        self.deletes += 1
        self.store.pop(h, None)


def test_remote_tier_codec_roundtrip_bf16():
    import ml_dtypes

    from dynamo_tpu.kvbm.tiers import RemoteTier

    k = np.arange(24, dtype=np.float32).reshape(2, 3, 2, 2).astype(
        ml_dtypes.bfloat16)
    v = (np.arange(24, dtype=np.float32) * 2).reshape(2, 3, 2, 2).astype(
        ml_dtypes.bfloat16)
    k2, v2 = RemoteTier.decode(RemoteTier.encode(k, v))
    assert k2.dtype == k.dtype and v2.shape == v.shape
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)


def test_remote_tier_reserve_evict_discard_accounting():
    """RemoteTier is only the index: reserve charges bytes and LRU-evicts
    past the budget (never the entry just reserved), touch refreshes,
    discard refunds exactly once."""
    from dynamo_tpu.kvbm.tiers import RemoteTier

    t = RemoteTier(client=None, capacity_bytes=300)
    assert t.reserve(1, 100) == []
    assert t.reserve(2, 100) == []
    assert t.reserve(3, 100) == []
    assert t.used == 300 and len(t) == 3
    t.reserve(1, 100)  # re-reserve: LRU refresh, no double charge
    assert t.used == 300
    assert t.reserve(4, 100) == [2]  # oldest untouched entry out
    assert t.used == 300 and 2 not in t and 1 in t
    t.touch(3)
    assert t.reserve(5, 100) == [1]  # touch saved 3; 1 now oldest
    t.discard(3)
    assert t.used == 200
    t.discard(3)  # double discard must not go negative
    assert t.used == 200
    # an over-budget single entry still reserves (len>1 guard: the tier
    # never evicts the entry it is reserving)
    big = RemoteTier(client=None, capacity_bytes=10)
    assert big.reserve(7, 100) == []
    assert 7 in big and big.used == 100
    assert set(big.clear()) == {7} and big.used == 0


def test_remote_tier_codec_roundtrip_int8():
    """Packed int8 KV blocks ([L, X] uint8 quant payload) survive the G4
    wire codec bit-exactly."""
    from dynamo_tpu.kvbm.tiers import RemoteTier

    rng = np.random.default_rng(0)
    k = rng.integers(0, 256, (2, 96), dtype=np.uint8)
    v = rng.integers(0, 256, (2, 96), dtype=np.uint8)
    k2, v2 = RemoteTier.decode(RemoteTier.encode(k, v))
    assert k2.dtype == np.uint8 and v2.dtype == np.uint8
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)


def test_drain_remote_order_and_retry():
    """_drain_remote performs queued G4 I/O strictly in queue order (a
    delete queued after a put can never run first), outside the manager
    lock, and parks failed deletes for the NEXT drain instead of
    hot-looping them."""
    from dynamo_tpu.kvbm.manager import KvbmManager

    calls = []

    class Client(_FakeG4Client):
        fail_deletes = 0

        def put(self, h, data):
            calls.append(("put", h))
            super().put(h, data)

        def delete(self, h):
            calls.append(("delete", h))
            if self.fail_deletes > 0:
                self.fail_deletes -= 1
                raise RuntimeError("plane flake")
            super().delete(h)

    client = Client()
    m = KvbmManager(host_bytes=1 << 20)
    m.attach_remote(client, capacity_bytes=0)
    k = page(1)
    with m._lock:
        m._to_remote(1, k, k)
        m._to_remote(2, k, k)
        # delete of 1 queued AFTER its put: order must hold through drain
        m._remote_ops.append(("delete", 1, None))
        m._pending_puts.discard(1)
        m.remote.discard(1)
    m._drain_remote()
    assert calls == [("put", 1), ("put", 2), ("delete", 1)]
    assert 1 not in client.store and 2 in client.store

    # failed delete parks for the next drain (bounded retries)
    calls.clear()
    client.fail_deletes = 1
    with m._lock:
        m._remote_ops.append(("delete", 2, None))
        m._pending_puts.discard(2)
        m.remote.discard(2)
    m._drain_remote()
    assert calls == [("delete", 2)]  # one attempt this drain, then parked
    assert m._remote_retry and 2 in client.store
    m._drain_remote()  # retry merged at the head of the next drain
    assert calls == [("delete", 2), ("delete", 2)]
    assert 2 not in client.store and not m._remote_retry


def test_g4_cascade_fetch_and_budget(tmp_path):
    """G2→G3→G4 cascade: disk evictions land in the object store with the
    bytes intact; get() falls all the way through and promotes; the G4
    byte budget LRU-evicts with remote deletes."""
    from dynamo_tpu.kvbm.manager import KvbmManager

    def blk(i):
        k = np.full((2, 4, 1, 4), i, np.float32)
        return k, k * 2

    from dynamo_tpu.kvbm.tiers import RemoteTier

    b = blk(0)[0].nbytes * 2
    payload_len = len(RemoteTier.encode(*blk(0)))
    client = _FakeG4Client()
    m = KvbmManager(host_bytes=2 * b, disk_dir=str(tmp_path), disk_bytes=2 * b)
    m.attach_remote(client, capacity_bytes=2 * payload_len)
    events = []
    m.on_change = lambda stored, removed: events.append((stored, removed))

    for i in range(8):  # host 2, disk 2 → 4 reach G4, budget 2 → overflow
        m.put(100 + i, *blk(i))
    st = m.stats()
    assert st["host_blocks"] == 2 and st["disk_blocks"] == 2
    assert st["remote_blocks"] == 2 and client.puts >= 2
    assert client.deletes >= 2  # LRU past the G4 budget deleted remotely
    # the oldest blocks fell out of G4's budget → reported fully removed
    removed_all = [h for _, rem in events if rem for h in rem]
    assert removed_all, "G4 budget eviction must be announced"
    # a G4-resident block fetches and promotes to host
    g4_hash = next(iter(client.store))
    got = m.get(g4_hash)
    assert got is not None
    i = g4_hash - 100
    np.testing.assert_array_equal(got[0], blk(i)[0])
    assert client.gets >= 1
    assert m.get_host(g4_hash) is not None  # promoted
    # clear() empties the remote store too
    m.clear()
    assert client.store == {} and m.stats()["remote_blocks"] == 0


async def test_offload_through_g4_determinism(tmp_path):
    """Determinism across a FULL tier flush: host AND disk sized so the
    prefix cascades into G4 (real in-process control plane object store);
    cleared device pool + repeated prompts still reproduce exactly."""
    from dynamo_tpu.kvbm.distributed import ObjectStoreG4Client
    from dynamo_tpu.runtime import DistributedRuntime

    prompt = list(range(1, 30))
    ref_eng = make_engine()
    want = await collect(ref_eng, req(prompt))
    await ref_eng.close()

    rt = await DistributedRuntime.create()
    cfg = ModelConfig.tiny()
    blk_bytes = 2 * cfg.num_layers * 4 * cfg.num_kv_heads * (
        cfg.hidden_size // cfg.num_heads) * 4
    eng = make_engine(kvbm_host_bytes=2 * blk_bytes,
                      kvbm_disk_dir=str(tmp_path),
                      kvbm_disk_bytes=2 * blk_bytes)
    class CountingClient(ObjectStoreG4Client):
        fetches = 0

        def get(self, h):
            CountingClient.fetches += 1
            return super().get(h)

    eng.kvbm.attach_remote(
        CountingClient(rt.plane, asyncio.get_event_loop()), 0)
    try:
        got1 = await collect(eng, req(prompt))
        assert got1 == want
        for _ in range(100):
            if eng.kvbm.stats()["remote_blocks"] > 0:
                break
            await asyncio.sleep(0.02)
        assert eng.kvbm.stats()["remote_blocks"] > 0  # cascaded to G4

        for round_ in range(3):
            eng.pool.clear()
            got = await collect(eng, req(prompt))
            assert got == want, f"round {round_}"
            await asyncio.sleep(0.05)  # let promotions land
        # blocks really came back from the object store at least once
        assert CountingClient.fetches > 0
    finally:
        await eng.close()
        await rt.shutdown()
