"""Network-aware disaggregation (docs/disagg.md): topology-costed KV
routing, layer-interleaved tail transfer, and the QoS-aware prefill pool.

The key properties: (1) the routing transfer term prefers near decode
workers exactly when locality labels exist and vanishes otherwise
(topology-blind default recoverable by config); (2) layer-split transfer
is bit-exact against aggregated serving on every transport, and a torn
layer assembly degrades to local recompute with exact token accounting;
(3) the prefill pool serves best-class-first and the claim fallback
prefers same-pod instances.
"""

import asyncio
import random

import pytest

from dynamo_tpu.disagg.handlers import (
    DecodeWorkerHandler, KV_LAYERS_ANNOTATION, PrefillWorkerHandler,
)
from dynamo_tpu.disagg.protocols import (
    DisaggConfig, KvBundle, KvChunkFrame, KvLayerFrame, PrefillResponse,
)
from dynamo_tpu.router.indexer import OverlapScores
from dynamo_tpu.router.protocols import KvRouterConfig
from dynamo_tpu.router.scheduler import KvScheduler
from dynamo_tpu.router.topology import (
    DEFAULT_GBPS, TopologyCostModel, TopologyLabels, link_class, link_costs,
)
from tests.test_disagg import collect_engine, make_engine, req

pytestmark = pytest.mark.anyio


# ------------------------------------------------------------- topology model

def test_link_class_matrix():
    a = TopologyLabels(host="h1", slice_id="s1", pod="p1")
    assert link_class(a, TopologyLabels(host="h1", slice_id="s1",
                                        pod="p1")) == "proc"
    assert link_class(a, TopologyLabels(host="h2", slice_id="s1",
                                        pod="p1")) == "ici"
    assert link_class(a, TopologyLabels(host="h2", slice_id="s2",
                                        pod="p1")) == "dcn"
    assert link_class(a, TopologyLabels(host="h2", slice_id="s2",
                                        pod="p2")) == "host"
    # unknown locality on either side is the conservative host class
    assert link_class(a, TopologyLabels()) == "host"
    assert link_class(TopologyLabels(), a) == "host"


def test_labels_env_and_metadata_roundtrip(monkeypatch):
    monkeypatch.delenv("DYN_TOPO_HOST", raising=False)
    monkeypatch.delenv("DYN_TOPO_SLICE", raising=False)
    monkeypatch.delenv("DYN_TOPO_POD", raising=False)
    assert not TopologyLabels.from_env()  # unset env = unlabeled fleet
    monkeypatch.setenv("DYN_TOPO_SLICE", "s7")
    monkeypatch.setenv("DYN_TOPO_POD", "p3")
    labels = TopologyLabels.from_env()
    assert labels and labels.slice_id == "s7" and labels.pod == "p3"
    assert labels.host  # defaults to the hostname when slice/pod are set
    meta = {"topo": labels.to_metadata()}
    back = TopologyLabels.from_metadata(meta)
    assert back.slice_id == "s7" and back.pod == "p3"
    assert not TopologyLabels.from_metadata(None)
    assert not TopologyLabels.from_metadata({"topo": "garbage"})


def test_cost_model_env_overrides(monkeypatch):
    m = TopologyCostModel()
    assert m.gbps == DEFAULT_GBPS
    assert m.rel_cost("ici") == 1.0
    assert m.rel_cost("host") > m.rel_cost("dcn") > m.rel_cost("ici")
    monkeypatch.setenv("DYN_TOPO_GBPS", "dcn=25, host=5")
    m2 = TopologyCostModel()
    assert m2.gbps["dcn"] == 25.0 and m2.gbps["host"] == 5.0
    assert m2.gbps["ici"] == DEFAULT_GBPS["ici"]
    # constructor overrides beat env
    m3 = TopologyCostModel({"dcn": 100.0})
    assert m3.gbps["dcn"] == 100.0
    monkeypatch.setenv("DYN_TOPO_GBPS", "warp=9")
    with pytest.raises(ValueError):
        TopologyCostModel()
    monkeypatch.setenv("DYN_TOPO_GBPS", "dcn=-1")
    with pytest.raises(ValueError):
        TopologyCostModel()


def test_link_costs_min_over_sources_and_blind_default():
    near = TopologyLabels(host="d1", slice_id="s0", pod="p0")
    far = TopologyLabels(host="d2", slice_id="s9", pod="p9")
    sources = [TopologyLabels(host="pp", slice_id="s0", pod="p0")]
    costs = link_costs(sources, {1: near, 2: far})
    assert costs[1] < costs[2]  # ici vs host
    # a second, far source must not worsen worker 1 (min over sources)
    costs2 = link_costs(sources + [far], {1: near, 2: far})
    assert costs2[1] == costs[1]
    assert costs2[2] < costs[2]  # far worker is proc-local to the far source
    # nobody labeled → None → the scheduler term vanishes (blind default)
    assert link_costs([TopologyLabels()], {1: near}) is None


# --------------------------------------------------------- scheduler term

def _schedule(link, weight=None, temp=0.0):
    cfg = KvRouterConfig(router_temperature=temp)
    if weight is not None:
        cfg.transfer_cost_weight = weight
    sched = KvScheduler(4, cfg, rng=random.Random(0))
    return sched.schedule("r1", isl_tokens=64, seq_hashes=None,
                          overlaps=OverlapScores(), worker_ids=[1, 2],
                          link_costs=link)


def test_scheduler_transfer_term_prefers_near_worker():
    for _ in range(8):  # no tie-break luck: near must win every time
        d = _schedule({1: 1.0, 2: 25.0})
        assert d.worker_id == 1
        assert d.logits[2] > d.logits[1]


def test_scheduler_blind_without_link_costs_and_weight_zero():
    d = _schedule(None)
    assert d.logits[1] == d.logits[2]  # no term at all
    d2 = _schedule({1: 1.0, 2: 25.0}, weight=0.0)
    assert d2.logits[1] == d2.logits[2]  # config kill-switch


def test_scheduler_missing_worker_prices_at_worst_link():
    """A worker that joined worker_ids after the topology snapshot (so it
    is absent from the cost map) must price at the WORST known link, not
    zero — unknown is conservatively far, never free."""
    for _ in range(8):
        # worker 2 is absent from the map; the worst known link is 25.0
        d = _schedule({1: 1.0, 3: 25.0})
        assert d.worker_id == 1
        assert d.logits[2] > d.logits[1]


def test_scheduler_transfer_term_override():
    cfg = KvRouterConfig()
    sched = KvScheduler(4, cfg, rng=random.Random(0))
    d = sched.schedule("r1", isl_tokens=64, seq_hashes=None,
                       overlaps=OverlapScores(), worker_ids=[1, 2],
                       router_config_override={"transfer_cost_weight": 0.0},
                       link_costs={1: 1.0, 2: 25.0})
    assert d.logits[1] == d.logits[2]


# ------------------------------------------------- layer-interleaved transfer

async def test_layer_bundle_wire_roundtrip():
    import msgpack
    import numpy as np

    k = np.arange(3 * 2 * 4 * 2 * 8, dtype=np.float32).reshape(3, 2, 4, 2, 8)
    b = KvBundle(k=k, v=k + 1, num_tokens=8, block_size=4, start_block=5,
                 start_layer=6, total_layers=12)
    w = msgpack.unpackb(msgpack.packb(KvLayerFrame(b).to_wire()), raw=False)
    assert KvLayerFrame.is_wire(w) and not KvChunkFrame.is_wire(w)
    b2 = KvLayerFrame.from_wire(w).bundle
    np.testing.assert_array_equal(b2.k, k)
    assert (b2.start_layer, b2.total_layers, b2.start_block) == (6, 12, 5)
    # full-depth bundles stay wire-identical to the pre-layer-split format
    plain = KvBundle(k=k, v=k, num_tokens=8, block_size=4).to_wire()
    assert "start_layer" not in plain and "total_layers" not in plain


class _SpyPrefillClient:
    """Routes to an in-process prefill handler, counting frame kinds."""

    def __init__(self, ph):
        self.ph = ph
        self.seen = {"layer": 0, "chunk": 0, "direct": 0}

    def available_ids(self):
        return [1]

    async def generate(self, request, ctx=None, mode="round_robin",
                       instance_id=None):
        from dynamo_tpu.disagg.transfer import KvDirectFrame

        async def stream():
            async for f in self.ph.generate(request, None):
                if KvLayerFrame.is_wire(f):
                    self.seen["layer"] += 1
                elif KvChunkFrame.is_wire(f):
                    self.seen["chunk"] += 1
                elif KvDirectFrame.is_wire(f):
                    self.seen["direct"] += 1
                yield f
        return stream()


async def test_layer_split_host_staged_bit_exact():
    """Host-staged layer frames reassemble to the exact aggregated tokens,
    and the final chunk rides layer frames (not a full-depth bundle)."""
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine(kv_transfer_direct=False)
    dec = make_engine(kv_transfer_direct=False)
    spy = _SpyPrefillClient(PrefillWorkerHandler(pre))
    dh = DecodeWorkerHandler(dec, spy,
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    # tiny has L=2 → min(4, 2) = 2 layer groups, and mid chunks still flow
    assert spy.seen["layer"] == 2 and spy.seen["chunk"] >= 1
    await pre.close()
    await dec.close()


async def test_layer_split_disabled_by_config():
    """kv_transfer_layer_groups<=1 on the decode side drops the capability
    annotation → the prefill side ships whole-bundle tails (recoverable
    topology-blind behavior, acceptance criterion)."""
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine(kv_transfer_direct=False)
    dec = make_engine(kv_transfer_direct=False, kv_transfer_layer_groups=0)
    spy = _SpyPrefillClient(PrefillWorkerHandler(pre))
    dh = DecodeWorkerHandler(dec, spy,
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert spy.seen["layer"] == 0 and spy.seen["chunk"] >= 2
    await pre.close()
    await dec.close()


async def test_layer_split_int8_host_staged_bit_exact():
    """Packed int8 layer slices over the host-staged wire scatter
    bit-exactly (the _scatter_packed_layers path)."""
    prompt = list(range(1, 151))
    agg = make_engine(kv_cache_dtype="int8")
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine(kv_cache_dtype="int8", kv_transfer_direct=False)
    dec = make_engine(kv_cache_dtype="int8", kv_transfer_direct=False)
    spy = _SpyPrefillClient(PrefillWorkerHandler(pre))
    dh = DecodeWorkerHandler(dec, spy,
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert spy.seen["layer"] >= 1
    await pre.close()
    await dec.close()


async def test_torn_layer_assembly_recomputes_locally():
    """Dropping one layer frame tears the tail assembly: the decode worker
    must recompute locally with exact tokens and leak no blocks."""
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine(kv_transfer_direct=False)
    dec = make_engine(kv_transfer_direct=False)
    free0 = dec.pool.num_free_blocks
    ph = PrefillWorkerHandler(pre)

    class DroppingClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            async def stream():
                dropped = False
                async for f in ph.generate(request, None):
                    if KvLayerFrame.is_wire(f) and not dropped:
                        dropped = True
                        continue  # lose the first layer group
                    yield f
            return stream()

    dh = DecodeWorkerHandler(dec, DroppingClient(),
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want  # exact token accounting through the fallback
    for _ in range(50):
        if dec.pool.num_free_blocks == free0 and not dec.scheduler.has_work:
            break
        await asyncio.sleep(0.02)
    assert dec.pool.num_free_blocks == free0
    await pre.close()
    await dec.close()


# --------------------------------------------- transfer fallback matrix

async def test_chaos_injected_pull_failure_recomputes_exactly(chaos):
    """Chaos at kv.direct_pull: every direct pull fails → the decode side
    drains, recomputes prefill locally, tokens match aggregated exactly,
    and the degradation is counted on /metrics."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    chaos("kv.direct_pull:error=1.0", seed=3)
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    free0 = dec.pool.num_free_blocks
    reg = MetricsRegistry()
    spy = _SpyPrefillClient(PrefillWorkerHandler(pre))
    dh = DecodeWorkerHandler(dec, spy,
                             DisaggConfig(max_local_prefill_length=8),
                             metrics=reg)
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert spy.seen["direct"] >= 1  # the direct path was really offered
    assert dec.direct_transfer.stats["pull_failures"] >= 1
    text = reg.render()
    assert "dynamo_kv_direct_pull_failures_total" in text
    failures = [ln for ln in text.splitlines()
                if ln.startswith("dynamo_kv_direct_pull_failures_total ")]
    assert failures and float(failures[0].split()[-1]) >= 1
    for _ in range(50):
        if dec.pool.num_free_blocks == free0 and not dec.scheduler.has_work:
            break
        await asyncio.sleep(0.02)
    assert dec.pool.num_free_blocks == free0
    await pre.close()
    await dec.close()


async def test_unplaceable_stream_retracts_direct_offers():
    """When the decode side cannot place pages (alloc failure), the drained
    direct offers are retracted immediately — no pages pinned until the
    TTL sweep — and the request completes via local prefill."""
    from dynamo_tpu.disagg import transfer as T

    T._offers.clear()
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    dec.alloc_inject = lambda n: None  # injection always refused
    spy = _SpyPrefillClient(PrefillWorkerHandler(pre))
    dh = DecodeWorkerHandler(dec, spy,
                             DisaggConfig(max_local_prefill_length=8))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert spy.seen["direct"] >= 2
    assert not T._offers  # every unclaimed offer was retracted
    await pre.close()
    await dec.close()


async def test_kv_transfer_metrics_host_path():
    """dynamo_kv_transfer_bytes_total{path=host} and the seconds histogram
    populate from a host-staged transfer."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    prompt = list(range(1, 151))
    pre = make_engine(kv_transfer_direct=False)
    dec = make_engine(kv_transfer_direct=False)
    reg = MetricsRegistry()
    dh = DecodeWorkerHandler(dec, _layer_client(pre),
                             DisaggConfig(max_local_prefill_length=8),
                             metrics=reg)
    async for _ in dh.generate(req(prompt).to_wire(), None):
        pass
    text = reg.render()
    byte_lines = [ln for ln in text.splitlines()
                  if ln.startswith("dynamo_kv_transfer_bytes_total{")]
    assert byte_lines and 'path="host"' in byte_lines[0]
    assert float(byte_lines[0].split()[-1]) > 0
    assert 'dynamo_kv_transfer_seconds_count{path="host"} 1' in text
    await pre.close()
    await dec.close()


def _layer_client(pre):
    return _SpyPrefillClient(PrefillWorkerHandler(pre))


# ------------------------------------------------- QoS-aware prefill pool

async def test_prefill_queue_best_class_first():
    """A capacity-1 worker must claim interactive → standard → batch no
    matter the enqueue order."""
    from dynamo_tpu.disagg.queue import (
        PrefillQueueClient, PrefillQueueWorker, prefill_queue_depth,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    client = PrefillQueueClient(plane, claim_timeout=5.0)

    order = []
    gate_open = asyncio.Event()

    acquires = []
    for prio in ("batch", "standard", "interactive"):  # worst first
        ctx = Context()
        ctx.priority = None if prio == "standard" else prio
        acquires.append(asyncio.ensure_future(client.acquire(ctx)))
        await asyncio.sleep(0.05)  # deterministic enqueue order
    assert await prefill_queue_depth(plane) == 3  # split queues still sum

    claimed = asyncio.Event()

    class RecordingWorker(PrefillQueueWorker):
        async def _pop_best_class(self):
            await gate_open.wait()
            item = await super()._pop_best_class()
            if item is not None:
                import msgpack

                order.append(msgpack.unpackb(item, raw=False).get(
                    "qos", "standard"))
                if len(order) == 3:
                    claimed.set()
            return item

    w = await RecordingWorker(plane, instance_id=42).start()
    gate_open.set()
    await asyncio.wait_for(claimed.wait(), 10.0)
    assert order == ["interactive", "standard", "batch"]
    for f in acquires:
        assert await f == 42
    await w.stop()
    await plane.close()


async def test_claim_fallback_prefers_same_pod_and_counts():
    """Claim timeout → fallback dispatch goes DIRECT to the near (same-pod)
    prefill instance, and the degradation is counted by reason."""
    from types import SimpleNamespace

    from dynamo_tpu.disagg.queue import PrefillQueueClient
    from dynamo_tpu.runtime.control_plane import LocalControlPlane
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    plane = LocalControlPlane()
    prompt = list(range(1, 151))
    agg = make_engine()
    want = await collect_engine(agg, req(prompt))
    await agg.close()

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)
    NEAR, FAR = 11, 22
    calls = []

    class LabeledClient:
        def available_ids(self):
            return [NEAR, FAR]

        def instances(self):
            return [
                SimpleNamespace(instance_id=NEAR, metadata={
                    "topo": {"host": "other", "slice": "s1", "pod": "p0"}}),
                SimpleNamespace(instance_id=FAR, metadata={
                    "topo": {"host": "far", "slice": "s9", "pod": "p9"}}),
            ]

        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            calls.append((mode, instance_id))

            async def stream():
                async for f in ph.generate(request, None):
                    yield f
            return stream()

    reg = MetricsRegistry()
    dh = DecodeWorkerHandler(
        dec, LabeledClient(), DisaggConfig(max_local_prefill_length=8),
        prefill_queue=PrefillQueueClient(plane, claim_timeout=0.05),
        metrics=reg,
        topo_labels=TopologyLabels(host="me", slice_id="s1", pod="p0"))
    got = []
    async for frame in dh.generate(req(prompt).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert got == want
    assert calls == [("direct", NEAR)]  # near preferred, not round robin
    line = next(ln for ln in reg.render().splitlines()
                if ln.startswith('dynamo_prefill_claim_fallback_total{'))
    assert 'reason="timeout"' in line and float(line.split()[-1]) == 1.0
    await pre.close()
    await dec.close()
    await plane.close()


async def test_nearest_pick_handles_mixed_labeled_pool():
    """Unlabeled prefill instances price at the host class, so a mixed
    pool still prefers the strictly-nearer labeled instance — and with NO
    queue configured the near preference must not run at all (a standing
    pin with no load signal would hot-spot one instance)."""
    from types import SimpleNamespace

    dec = make_engine()
    NEAR, BARE = 5, 6

    class MixedClient:
        def available_ids(self):
            return [NEAR, BARE]

        def instances(self):
            return [
                SimpleNamespace(instance_id=NEAR, metadata={
                    "topo": {"host": "x", "slice": "s1", "pod": "p0"}}),
                SimpleNamespace(instance_id=BARE, metadata={}),
            ]

    dh = DecodeWorkerHandler(
        dec, MixedClient(), DisaggConfig(max_local_prefill_length=8),
        topo_labels=TopologyLabels(host="me", slice_id="s1", pod="p0"))
    assert dh._nearest_prefill_instance() == NEAR
    await dec.close()


async def test_no_queue_deployment_keeps_round_robin():
    """prefill_queue=None (the r1 dispatch path): even a labeled pool must
    be served round robin — the near preference is a CLAIM-FALLBACK
    behavior only."""
    from types import SimpleNamespace

    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)
    calls = []

    class LabeledClient:
        def available_ids(self):
            return [1, 2]

        def instances(self):
            return [SimpleNamespace(instance_id=i, metadata={
                "topo": {"host": f"h{i}", "slice": "s1", "pod": "p0"}})
                for i in (1, 2)]

        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            calls.append(mode)

            async def stream():
                async for f in ph.generate(request, None):
                    yield f
            return stream()

    dh = DecodeWorkerHandler(
        dec, LabeledClient(), DisaggConfig(max_local_prefill_length=8),
        topo_labels=TopologyLabels(host="h1", slice_id="s1", pod="p0"))
    got = []
    async for frame in dh.generate(req(list(range(1, 151))).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert len(got) == 8
    assert calls == ["round_robin"]
    await pre.close()
    await dec.close()


async def test_claim_fallback_unlabeled_pool_stays_round_robin():
    from dynamo_tpu.disagg.queue import PrefillQueueClient
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    plane = LocalControlPlane()
    pre = make_engine()
    dec = make_engine()
    ph = PrefillWorkerHandler(pre)
    modes = []

    class PlainClient:
        def available_ids(self):
            return [1]

        async def generate(self, request, ctx=None, mode="round_robin",
                           instance_id=None):
            modes.append(mode)

            async def stream():
                async for f in ph.generate(request, None):
                    yield f
            return stream()

    dh = DecodeWorkerHandler(
        dec, PlainClient(), DisaggConfig(max_local_prefill_length=8),
        prefill_queue=PrefillQueueClient(plane, claim_timeout=0.05),
        topo_labels=TopologyLabels(host="me", slice_id="s1", pod="p0"))
    got = []
    async for frame in dh.generate(req(list(range(1, 151))).to_wire(), None):
        got.extend(frame.get("token_ids", []))
    assert len(got) == 8
    assert modes == ["round_robin"]
    await pre.close()
    await dec.close()
    await plane.close()


# ------------------------------------------------------ router integration

async def test_push_router_link_costs_from_instance_metadata():
    """KvPushRouter folds prefill-pool + decode-worker labels into link
    costs; unlabeled pools and the weight kill-switch return None."""
    from types import SimpleNamespace

    from dynamo_tpu.router.kv_router import KvPushRouter

    def fake_client(instances):
        c = SimpleNamespace()
        c.instances = lambda: instances
        return c

    near = SimpleNamespace(instance_id=1, metadata={
        "topo": {"host": "a", "slice": "s0", "pod": "p0"}})
    far = SimpleNamespace(instance_id=2, metadata={
        "topo": {"host": "b", "slice": "s8", "pod": "p8"}})
    pool = [SimpleNamespace(instance_id=9, metadata={
        "topo": {"host": "pp", "slice": "s0", "pod": "p0"}})]

    router = SimpleNamespace(config=KvRouterConfig())
    pr = KvPushRouter.__new__(KvPushRouter)
    pr.client = fake_client([near, far])
    pr.router = router
    pr.prefill_client = fake_client(pool)
    pr._topo_model = None
    pr._link_cache = None
    costs = pr._link_costs()
    assert costs[1] < costs[2]
    assert pr._link_costs() is costs  # memoized on instance identity

    pr.router = SimpleNamespace(config=KvRouterConfig(
        transfer_cost_weight=0.0))
    assert pr._link_costs() is None  # config kill-switch

    pr.router = router
    pr.prefill_client = fake_client([SimpleNamespace(
        instance_id=9, metadata={})])
    assert pr._link_costs() is None  # unlabeled pool: blind default

    pr.prefill_client = None
    assert pr._link_costs() is None  # aggregated deployment


async def test_serve_endpoint_stamps_topo_metadata(monkeypatch):
    """Workers publish DYN_TOPO_* locality labels in their instance record
    at registration (runtime/component.py)."""
    from dynamo_tpu.runtime import DistributedRuntime

    monkeypatch.setenv("DYN_TOPO_SLICE", "s5")
    monkeypatch.setenv("DYN_TOPO_POD", "p5")
    rt = await DistributedRuntime.create()
    try:
        ep = rt.namespace("topo-test").component("w").endpoint("generate")

        async def handler(request, ctx):
            yield {"ok": True}

        handle = await ep.serve_endpoint(handler)
        client = await ep.client().start()
        inst = client.instances()[0]
        assert inst.metadata["topo"] == {
            "host": TopologyLabels.from_env().host,
            "slice": "s5", "pod": "p5"}
        await client.stop()
        await handle.stop(graceful=False)
    finally:
        await rt.shutdown()


# ------------------------------------------------------- placement A/B smoke

async def test_fleet_ab_smoke():
    """The multi-worker placement A/B runs on CPU and topology-aware
    placement lands every foreground request on the near pod."""
    from benchmarks.disagg_ab import fleet_ab

    out = await fleet_ab(prefill_workers=1, decode_workers=2, isl=64,
                         osl=4, fg=4, seed=0)
    assert out["topo_near_share"] == 1.0
    assert out["blind_ttft_p95_s"] > 0 and out["topo_ttft_p95_s"] > 0
    # the far link is ~25x slower; even p50 should separate cleanly, but
    # no margin is gated here: these are host timings of the tiny preset
    assert out["ttft_p95_ratio_blind_over_topo"] is not None
