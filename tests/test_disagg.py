"""Disaggregated prefill/decode: KV-transfer correctness and handler flows.

The key property (the reference tests it as KVBM/disagg determinism —
tests/kvbm/test_determinism.py): a request served disaggregated — prefill on
engine A, KV bundle shipped, decode on engine B — must produce exactly the
tokens the aggregated path produces.
"""

import asyncio

import pytest

from dynamo_tpu.disagg.handlers import DecodeWorkerHandler, PrefillWorkerHandler
from dynamo_tpu.disagg.protocols import DisaggConfig, KvBundle, PrefillResponse
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


def make_engine(**kw) -> AsyncJaxEngine:
    cfg = ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=8,
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


async def collect_engine(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
    return toks


async def test_kv_bundle_wire_roundtrip():
    import numpy as np

    k = np.arange(2 * 3 * 4 * 2 * 8, dtype=np.float32).reshape(2, 3, 4, 2, 8)
    b = KvBundle(k=k, v=k + 1, num_tokens=11, block_size=4)
    import msgpack

    w = msgpack.unpackb(msgpack.packb(b.to_wire()), raw=False)
    b2 = KvBundle.from_wire(w)
    np.testing.assert_array_equal(b2.k, k)
    np.testing.assert_array_equal(b2.v, k + 1)
    assert b2.num_tokens == 11 and b2.block_size == 4


@pytest.mark.slow
async def test_disagg_matches_aggregated():
    """prefill_extract on engine A + generate_injected on engine B must equal
    engine C's aggregated generate, token for token."""
    prompt = list(range(1, 23))  # 22 tokens: ends mid-block (block_size 4)

    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()
    assert len(want) == 8

    pre = make_engine()
    dec = make_engine()
    presp = await pre.prefill_extract(req(prompt))
    assert presp.token_id == want[0]
    assert presp.bundle is not None and presp.bundle.num_tokens == len(prompt)
    # wire round-trip like the real path does
    import msgpack
    presp2 = PrefillResponse.from_wire(
        msgpack.unpackb(msgpack.packb(presp.to_wire()), raw=False))

    got = []
    async for out in dec.generate_injected(req(prompt), presp2):
        got.extend(out.token_ids)
    assert got == want
    await pre.close()
    await dec.close()


async def test_prefill_blocks_released_after_extract():
    eng = make_engine()
    free0 = eng.pool.num_free_blocks
    presp = await eng.prefill_extract(req(list(range(1, 23))))
    assert presp.bundle is not None
    assert eng.pool.num_free_blocks == free0  # held blocks returned
    await eng.close()


@pytest.mark.slow
async def test_handlers_end_to_end_local_client():
    """PrefillWorkerHandler + DecodeWorkerHandler over a fake client."""
    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)

    class FakePrefillClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin"):
            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(dec, FakePrefillClient(),
                             DisaggConfig(max_local_prefill_length=8))
    prompt = list(range(1, 23))  # > threshold → remote prefill

    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    got, reasons = [], []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
        if frame.get("finish_reason"):
            reasons.append(frame["finish_reason"])
    assert got == want
    assert reasons == [FinishReason.LENGTH]

    # short prompt stays local
    short = list(range(1, 6))
    agg2 = make_engine()
    want2 = await collect_engine(agg2, req(short))
    await agg2.close()
    got2 = []
    async for frame in dh.generate(req(short).to_wire(), None):
        got2.extend(frame.get("token_ids", []))
    assert got2 == want2

    await pre.close()
    await dec.close()


async def test_pipelined_prefill_stream_chunks_then_final():
    """A multi-chunk prompt must ship KvChunkFrames BEFORE the final
    PrefillResponse (transfer overlapped with prefill compute), and the
    streamed frames must reassemble into the exact aggregated KV."""
    from dynamo_tpu.disagg.protocols import KvChunkFrame

    prompt = list(range(1, 151))  # 150 tokens, chunks of 64 → 2 mid frames
    pre = make_engine()
    frames = []
    async for w in pre.prefill_extract_stream(req(prompt)):
        frames.append(w)
    await pre.close()
    chunk_frames = [f for f in frames if KvChunkFrame.is_wire(f)]
    assert len(chunk_frames) >= 2  # blocks shipped while prefill ran
    assert not KvChunkFrame.is_wire(frames[-1])
    final = PrefillResponse.from_wire(frames[-1])
    assert final.token_id >= 0
    # contiguous coverage: chunks then tail cover ceil(150/4) blocks
    nxt = 0
    for f in chunk_frames:
        b = KvChunkFrame.from_wire(f).bundle
        assert b.start_block == nxt
        nxt += b.k.shape[1]
    assert final.bundle is not None and final.bundle.start_block == nxt
    assert nxt + final.bundle.k.shape[1] == (len(prompt) + 3) // 4


@pytest.mark.slow
async def test_pipelined_disagg_matches_aggregated():
    """Full handler flow with streamed chunk scatter == aggregated tokens."""
    prompt = list(range(1, 151))

    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)

    class FakePrefillClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin"):
            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(dec, FakePrefillClient(),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    # decode-side blocks released when the request finished
    await pre.close()
    await dec.close()


@pytest.mark.slow
async def test_pipelined_disagg_mismatch_falls_back_local():
    """A decode engine that can't place the chunks (block-size mismatch)
    must drain the stream and recompute locally — same tokens, no leak."""
    prompt = list(range(1, 151))
    agg = make_engine(block_size=8)
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()  # block_size 4 → chunk frames won't place below
    dec = make_engine(block_size=8)
    free0 = dec.pool.num_free_blocks
    ph = PrefillWorkerHandler(pre)

    class FakePrefillClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin"):
            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(dec, FakePrefillClient(),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    for _ in range(50):
        if dec.pool.num_free_blocks == free0:
            break
        await asyncio.sleep(0.02)
    assert dec.pool.num_free_blocks == free0
    await pre.close()
    await dec.close()


async def test_pipelined_stream_failure_releases_injected_blocks():
    """Prefill stream dying after chunk frames landed must not leak the
    decode-side injected blocks (mid-stream failure surfaces upstream)."""
    from dynamo_tpu.disagg.protocols import KvChunkFrame
    from dynamo_tpu.disagg.transfer import KvDirectFrame

    prompt = list(range(1, 151))
    pre = make_engine()
    dec = make_engine()
    free0 = dec.pool.num_free_blocks
    ph = PrefillWorkerHandler(pre)

    class DyingPrefillClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin"):
            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
                    if (KvChunkFrame.is_wire(frame)
                            or KvDirectFrame.is_wire(frame)):
                        raise ConnectionError("prefill worker died")
            return stream()

    dh = DecodeWorkerHandler(dec, DyingPrefillClient(),
                             DisaggConfig(max_local_prefill_length=8))
    # no tokens were yielded before the failure → handler falls back local
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert len(got) == 8
    for _ in range(50):
        if dec.pool.num_free_blocks == free0 and not dec.scheduler.has_work:
            break
        await asyncio.sleep(0.02)
    assert dec.pool.num_free_blocks == free0
    await pre.close()
    await dec.close()


async def test_prefill_extract_cancelled_releases_blocks():
    """Cancelling prefill_extract mid-flight must not leak held blocks."""
    eng = make_engine()
    free0 = eng.pool.num_free_blocks
    task = asyncio.create_task(eng.prefill_extract(req(list(range(1, 60)))))
    await asyncio.sleep(0)  # let it enqueue
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    # the scheduler reaps the aborted seq on its next plan; poke the loop
    for _ in range(50):
        if eng.pool.num_free_blocks == free0 and not eng.scheduler.has_work:
            break
        await asyncio.sleep(0.02)
    assert eng.pool.num_free_blocks == free0
    await eng.close()


@pytest.mark.slow
async def test_prefill_queue_dispatch_end_to_end():
    """Queued dispatch (r1 verdict item #7): decode enqueues a ticket, the
    prefill worker pops + claims, KV streams direct — tokens match
    aggregated, and the queue drains to zero for the depth gauge."""
    from dynamo_tpu.disagg.queue import (
        PREFILL_QUEUE, PrefillQueueClient, PrefillQueueWorker,
        engine_capacity_gate,
    )
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)
    PRE_ID = 7001

    class DirectOnlyPrefillClient:
        """Fails unless the queue claim routed mode=direct to PRE_ID."""

        def available_ids(self):
            return [PRE_ID]

        async def generate(self, request, ctx=None, mode="round_robin", instance_id=None):
            assert mode == "direct" and instance_id == PRE_ID, \
                f"expected queued direct dispatch, got {mode}/{instance_id}"

            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
            return stream()

    qw = await PrefillQueueWorker(
        plane, instance_id=PRE_ID,
        capacity_gate=engine_capacity_gate(pre)).start()
    dh = DecodeWorkerHandler(
        dec, DirectOnlyPrefillClient(),
        DisaggConfig(max_local_prefill_length=8),
        prefill_queue=PrefillQueueClient(plane))

    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert qw.claims == 1
    assert await plane.queue_depth(PREFILL_QUEUE) == 0  # drained

    await qw.stop()
    await pre.close()
    await dec.close()
    await plane.close()


async def test_prefill_queue_claim_timeout_falls_back_round_robin():
    """No queue worker popping → claim times out → round-robin fallback."""
    from dynamo_tpu.disagg.queue import PrefillQueueClient
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)
    modes = []

    class RecordingClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin", instance_id=None):
            modes.append(mode)

            async def stream():
                async for frame in ph.generate(request, None):
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(
        dec, RecordingClient(), DisaggConfig(max_local_prefill_length=8),
        prefill_queue=PrefillQueueClient(plane, claim_timeout=0.1))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert modes == ["round_robin"]
    await pre.close()
    await dec.close()
    await plane.close()


async def test_disagg_threshold_watched_from_control_plane():
    """The conditional-disagg threshold updates live from the KV store
    (ref: disagg_router.rs:26-80)."""
    from dynamo_tpu.disagg.handlers import DisaggConfigWatcher
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    cfg = DisaggConfig(max_local_prefill_length=512)
    w = await DisaggConfigWatcher(plane, cfg).start()
    await plane.kv_put(DisaggConfig.KEY, b"128")
    for _ in range(100):
        if cfg.max_local_prefill_length == 128:
            break
        await asyncio.sleep(0.01)
    assert cfg.max_local_prefill_length == 128
    await plane.kv_put(DisaggConfig.KEY, b"not-a-number")  # ignored
    await asyncio.sleep(0.05)
    assert cfg.max_local_prefill_length == 128
    await w.stop()
    await plane.close()

    # pre-existing value applies at start
    plane2 = LocalControlPlane()
    await plane2.kv_put(DisaggConfig.KEY, b"64")
    cfg2 = DisaggConfig()
    w2 = await DisaggConfigWatcher(plane2, cfg2).start()
    assert cfg2.max_local_prefill_length == 64
    await w2.stop()
    await plane2.close()


# ------------------------------------------------- direct (NIXL-analog) path

class _LocalPrefillClient:
    """Routes decode→prefill calls to an in-process PrefillWorkerHandler."""

    def __init__(self, ph):
        self.ph = ph

    def available_ids(self):
        return [1]

    async def generate(self, request, ctx=None, mode="round_robin", instance_id=None):
        async def stream():
            async for frame in self.ph.generate(request, None):
                yield frame
        return stream()


@pytest.mark.slow
async def test_direct_transfer_same_process_matches_aggregated():
    """Co-located prefill+decode negotiate the zero-copy direct path: only
    descriptor frames cross the wire (no page bytes), the decode engine
    pulls device arrays from the in-process registry, and the tokens equal
    the aggregated run's exactly."""
    from dynamo_tpu.disagg import transfer as T
    from dynamo_tpu.disagg.transfer import KvDirectFrame

    # earlier fallback tests may have parked offers (TTL-swept in prod)
    T._offers.clear()

    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)

    seen = {"direct": 0, "chunk": 0}

    class SpyClient(_LocalPrefillClient):
        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            from dynamo_tpu.disagg.protocols import KvChunkFrame

            async def stream():
                async for frame in self.ph.generate(request, None):
                    if KvDirectFrame.is_wire(frame):
                        seen["direct"] += 1
                    elif KvChunkFrame.is_wire(frame):
                        seen["chunk"] += 1
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(dec, SpyClient(ph),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert seen["direct"] >= 2 and seen["chunk"] == 0
    assert pre.direct_transfer.stats["offers"] == seen["direct"]
    assert dec.direct_transfer.stats["pulls"] == seen["direct"]
    # every offer was claimed — nothing parked in the registry
    from dynamo_tpu.disagg import transfer as T
    assert not T._offers
    await pre.close()
    await dec.close()


async def test_direct_disabled_uses_host_staged_bundles():
    """kv_transfer_direct=False on the decode side → no capability
    annotation → prefill ships host-staged KvChunkFrames (the DCN path)."""
    from dynamo_tpu.disagg.protocols import KvChunkFrame
    from dynamo_tpu.disagg.transfer import KvDirectFrame

    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine(kv_transfer_direct=False)
    ph = PrefillWorkerHandler(pre)
    seen = {"direct": 0, "chunk": 0}

    class SpyClient(_LocalPrefillClient):
        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            async def stream():
                async for frame in self.ph.generate(request, None):
                    if KvDirectFrame.is_wire(frame):
                        seen["direct"] += 1
                    elif KvChunkFrame.is_wire(frame):
                        seen["chunk"] += 1
                    yield frame
            return stream()

    dh = DecodeWorkerHandler(dec, SpyClient(ph),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert seen["chunk"] >= 2 and seen["direct"] == 0
    await pre.close()
    await dec.close()


async def test_direct_pull_failure_falls_back_local():
    """A decode worker whose pulls fail (expired offer / dead server) must
    drain the stream, recompute prefill locally, and leak nothing."""
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    free0 = dec.pool.num_free_blocks

    def boom(desc):
        raise RuntimeError("synthetic pull failure")

    dec.direct_transfer.pull = boom
    ph = PrefillWorkerHandler(pre)
    dh = DecodeWorkerHandler(dec, _LocalPrefillClient(ph),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    for _ in range(50):
        if dec.pool.num_free_blocks == free0 and not dec.scheduler.has_work:
            break
        await asyncio.sleep(0.02)
    assert dec.pool.num_free_blocks == free0
    await pre.close()
    await dec.close()


async def test_direct_transfer_int8_kv_bit_exact():
    """int8 KV caches on both ends: the direct path ships PACKED (q,s)
    device bundles and the scatter is bit-exact — disagg tokens equal the
    aggregated int8 run's."""
    prompt = list(range(1, 151))
    agg = make_engine(kv_cache_dtype="int8")
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine(kv_cache_dtype="int8")
    dec = make_engine(kv_cache_dtype="int8")
    ph = PrefillWorkerHandler(pre)
    dh = DecodeWorkerHandler(dec, _LocalPrefillClient(ph),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert pre.direct_transfer.stats["offers"] >= 1
    assert dec.direct_transfer.stats["pulls"] >= 1
    await pre.close()
    await dec.close()


async def test_direct_offer_registry_ttl_eviction():
    """Unclaimed same-process offers (decode fell back) are swept after the
    TTL instead of pinning gathered pages forever."""
    import numpy as np

    from dynamo_tpu.disagg import transfer as T

    mgr = T.DirectTransferManager(ttl_s=0.01)
    desc = mgr.offer("proc", [np.zeros((2, 2))],
                     {"num_tokens": 4, "block_size": 4, "start_block": 0})
    assert desc["uuid"] in T._offers
    import time
    time.sleep(0.02)
    # the sweep rides the next offer
    mgr.offer("proc", [np.zeros((2, 2))],
              {"num_tokens": 4, "block_size": 4, "start_block": 0})
    assert desc["uuid"] not in T._offers
    # explicit retract drops immediately
    d2 = mgr.offer("proc", [np.zeros((2, 2))],
                   {"num_tokens": 4, "block_size": 4, "start_block": 0})
    mgr.retract(d2)
    assert d2["uuid"] not in T._offers
    with pytest.raises(RuntimeError):
        mgr.pull(d2)
    assert mgr.stats["pull_failures"] == 1
    T._offers.clear()


async def test_direct_capability_negotiation():
    """Mode selection: same proc → "proc"; cross-proc CPU → host-staged
    (None); no capability → None."""
    from dynamo_tpu.disagg import transfer as T

    mgr = T.DirectTransferManager()
    assert mgr.choose_mode([mgr.capability()]) == "proc"
    assert mgr.choose_mode(["kv_direct:otherhost:1:deadbeef/cpu"]) is None
    assert mgr.choose_mode(["kv_chunks"]) is None
    assert mgr.choose_mode(None) is None
    # TPU↔TPU cross-process advertises the transfer-server path
    other = "kv_direct:otherhost:1:deadbeef/tpu"
    import unittest.mock as mock
    with mock.patch.object(T, "_platform", return_value="tpu"):
        assert mgr.choose_mode([other]) == "ici"
    with mock.patch.object(T, "_platform", return_value="cpu"):
        assert mgr.choose_mode([other]) is None  # cpu end: host-staged
