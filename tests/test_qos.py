"""Multi-tenant QoS (ISSUE 5): priority classes, weighted-fair token
scheduling, swap-backed priority preemption.

The hard guarantees covered here:

- deterministic weighted fairness: two equal-weight tenants under
  saturation receive served-token counts within 10% of each other;
  2:1 weights split within 10% of 2:1 (scheduler-level driver, seeded);
- priority preemption proof: under KV/slot pressure with mixed classes,
  ONLY batch-class sequences are preempted while interactive streams stay
  bit-identical to an unloaded run (the test_swap equivalence harness);
- the swapped-deque starvation guard: a head-of-line swap-in candidate
  that keeps failing its block reservation is skipped after N attempts
  (dynamo_swap_in_blocked_total);
- per-tenant quotas at the frontend: token-rate 429s carry a Retry-After
  derived from the bucket refill time; overload 429s derive theirs from
  the observed drain rate, clamped to [1, 30] s;
- the router's class-biased cost: interactive flees saturated workers,
  batch chases cache overlap;
- wire compatibility: a pre-QoS peer (fields absent) interoperates with a
  QoS frontend/worker in both directions.
"""

import asyncio
import itertools
import time

import pytest

from dynamo_tpu.engine.cache import BlockPool
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.engine.scheduler import (
    SWAP_IN_SKIP_AFTER, Scheduler, SeqState,
)
from dynamo_tpu.protocols import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.qos import (
    DEFAULT_CLASS, QosConfig, TenantPolicy, normalize_priority,
)
from dynamo_tpu.qos.quota import (
    DrainRateEstimator, TenantQuotas, TokenBucket, clamp_retry_after,
)
from dynamo_tpu.runtime.config import ConfigError
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.anyio

BS = 4


# ----------------------------------------------------------- policy config


def test_normalize_priority():
    assert normalize_priority(None) == DEFAULT_CLASS
    assert normalize_priority("interactive") == "interactive"
    assert normalize_priority(" BATCH ") == "batch"
    assert normalize_priority("vip-gold") == DEFAULT_CLASS  # fallback + warn
    # caller-supplied fallback (frontend passes the tenant's own class)
    assert normalize_priority("vip-gold", default="batch") == "batch"
    assert normalize_priority(None, default="batch") == "batch"


def test_qos_config_env_loading_and_validation():
    cfg = QosConfig.load(env={
        "DYN_QOS_WEIGHTS": "interactive=8,standard=2,batch=1",
        "DYN_QOS_AGING_S": "5",
        "DYN_QOS_TENANT_RATE": "100",
        "DYN_QOS_TENANTS": (
            '{"acme": {"priority": "interactive", "rate": 500, '
            '"max_inflight": 2, "weight": 16, "api_keys": ["sk-acme"]}}'),
    })
    assert cfg.weights["interactive"] == 8.0
    assert cfg.aging_s == 5.0
    assert cfg.tenant_for_api_key("sk-acme") == "acme"
    assert cfg.tenant_for_api_key("sk-nope") is None
    assert cfg.default_priority("acme") == "interactive"
    assert cfg.default_priority("other") == DEFAULT_CLASS
    assert cfg.weight_for("acme", "batch") == 16.0  # tenant override wins
    assert cfg.weight_for("other", "interactive") == 8.0
    assert cfg.rate_for("acme") == (500.0, 2000.0)  # burst defaults to 4x
    assert cfg.rate_for("other") == (100.0, 400.0)
    assert cfg.max_inflight_for("acme") == 2
    assert cfg.max_adhoc_tenants == 1024  # bounded by default
    assert QosConfig.load(
        env={"DYN_QOS_MAX_TENANTS": "7"}).max_adhoc_tenants == 7

    with pytest.raises(ConfigError):
        QosConfig.load(env={"DYN_QOS_MAX_TENANTS": "-1"})
    with pytest.raises(ConfigError):
        QosConfig.load(env={"DYN_QOS_WEIGHTS": "gold=2"})
    with pytest.raises(ConfigError):
        QosConfig.load(env={"DYN_QOS_WEIGHTS": "interactive=-1"})
    with pytest.raises(ConfigError):
        QosConfig.load(env={"DYN_QOS_TENANTS": "not json"})
    with pytest.raises(ConfigError):
        QosConfig.load(env={
            "DYN_QOS_TENANTS": '{"a": {"priority": "vip"}}'})
    with pytest.raises(ConfigError):
        QosConfig.load(env={"DYN_QOS_TENANTS": '{"a": {"typo_key": 1}}'})


# ---------------------------------------------------------------- quotas


def test_token_bucket_and_retry_after():
    clock = [0.0]
    b = TokenBucket(rate=10.0, burst=100.0, clock=lambda: clock[0])
    assert b.try_take(60) is None
    wait = b.try_take(60)  # 40 left: 20-token deficit at 10 tok/s = 2 s
    assert wait == pytest.approx(2.0)
    clock[0] += 2.0
    assert b.try_take(60) is None
    # a cost above the whole burst reports time-to-FULL, clamped later
    huge = TokenBucket(rate=1.0, burst=10.0, clock=lambda: clock[0])
    assert clamp_retry_after(huge.try_take(10_000) or 0) <= 30

    assert clamp_retry_after(0.2) == 1
    assert clamp_retry_after(7.01) == 8
    assert clamp_retry_after(1e9) == 30
    assert clamp_retry_after(float("inf")) == 30


def test_tenant_quotas_rate_and_inflight():
    clock = [0.0]
    cfg = QosConfig(tenant_rate=10.0, tenant_burst=20.0,
                    tenant_max_inflight=2)
    q = TenantQuotas(cfg, clock=lambda: clock[0])
    assert q.admit("a", 15) is None
    reason, ra = q.admit("a", 15)  # 5 left: 10-token deficit = 1 s
    assert reason == "tenant_rate" and 1 <= ra <= 30
    # an unrelated tenant has its own bucket
    assert q.admit("b", 15) is None
    # inflight cap
    q.begin("a"), q.begin("a")
    clock[0] += 100.0  # bucket refilled; inflight still capped
    reason, _ = q.admit("a", 1)
    assert reason == "tenant_inflight"
    q.end("a")
    assert q.admit("a", 1) is None


def test_drain_rate_estimator():
    clock = [0.0]
    est = DrainRateEstimator(clock=lambda: clock[0])
    assert est.retry_after_s(5) == 1  # no signal: the old constant
    for _ in range(11):  # 10 completions over 5 s -> 2 req/s
        est.note()
        clock[0] += 0.5
    clock[0] -= 0.5  # sample exactly at the last completion (age 0)
    assert est.rate() == pytest.approx(2.0, rel=0.2)
    assert est.retry_after_s(4) == 2
    assert est.retry_after_s(1000) == 30  # clamp


# ----------------------------------------------- deterministic fairness


class _Ctx:
    cancelled = False
    expired = False

    def __init__(self, tenant, priority):
        self.tenant = tenant
        self.priority = priority
        self.id = f"{tenant}-{priority}"


class _Sink:
    def put_nowait(self, item):
        pass


_counter = itertools.count()


def _seq(tenant, cls, isl=16):
    req = PreprocessedRequest(
        model="t", token_ids=list(range(1, isl + 1)),
        stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
    return SeqState(request_id=f"{tenant}-{next(_counter)}", req=req,
                    ctx=_Ctx(tenant, cls), sink=_Sink())


def _sched(qos_cfg=None, num_blocks=1024, max_num_seqs=1,
           qos_scheduling=True):
    args = EngineArgs(block_size=BS, num_blocks=num_blocks,
                      max_num_seqs=max_num_seqs,
                      max_num_batched_tokens=64, max_model_len=1024,
                      enable_prefix_caching=False, preempt_swap=False,
                      qos_scheduling=qos_scheduling, qos=qos_cfg)
    return Scheduler(args, BlockPool(num_blocks, False))


def _drive(sched, tenants, steps=400, isl=16, osl=8):
    """Closed-loop saturation: every tenant keeps 2 requests waiting; each
    plan() is serviced synchronously (commit + sample). Deterministic —
    no wall-clock, no randomness."""
    def top_up():
        queued = {t: 0 for t, _c in tenants}
        for s in sched.waiting:
            queued[s.tenant] = queued.get(s.tenant, 0) + 1
        for tenant, cls in tenants:
            while queued[tenant] < 2:
                sched.add(_seq(tenant, cls, isl))
                queued[tenant] += 1

    top_up()
    for _ in range(steps):
        plan = sched.plan()
        for w in plan.prefill:
            sched.commit_computed(w.seq, w.start + w.chunk)
            if w.sample:
                sched.append_token(w.seq, 5)
        for s in plan.decode:
            sched.commit_computed(s, s.num_computed + 1)
            sched.append_token(s, 5)
        for s in list(sched.running):
            if s.generated >= osl:
                sched.finish(s, FinishReason.LENGTH)
        top_up()
    return sched.qos.served_tokens


def test_fairness_equal_weights_within_10pct():
    sched = _sched(QosConfig())
    served = _drive(sched, [("a", "standard"), ("b", "standard")])
    a, b = served[("a", "standard")], served[("b", "standard")]
    assert a > 0 and b > 0
    assert abs(a - b) / max(a, b) <= 0.10, served


def test_fairness_2to1_weights_within_10pct():
    cfg = QosConfig(tenants={"a": TenantPolicy(weight=2.0),
                             "b": TenantPolicy(weight=1.0)})
    sched = _sched(cfg)
    served = _drive(sched, [("a", "standard"), ("b", "standard")])
    ratio = served[("a", "standard")] / served[("b", "standard")]
    assert 2 * 0.9 <= ratio <= 2 * 1.1, served


def test_fairness_fifo_mode_is_order_preserving():
    """qos_scheduling=False: strict arrival order regardless of tenants —
    the pre-QoS scheduler, bit-for-bit."""
    sched = _sched(qos_scheduling=False, max_num_seqs=1)
    first, second = _seq("b", "batch", isl=8), _seq("a", "interactive", isl=8)
    sched.add(first)
    sched.add(second)
    plan = sched.plan()
    assert plan.prefill and plan.prefill[0].seq is first


def test_fifo_mode_ignores_aging():
    """qos_scheduling=False is the documented strict-arrival drain (the
    bench FIFO baseline): the aging escape hatch must not let a
    long-enqueued head jump a recompute-preempted victim whose appendleft
    kept its original arrival but reset its enqueue stamp."""
    from types import SimpleNamespace

    from dynamo_tpu.qos.fair import ClassQueues, QosBook

    def make(arrival_first, fresh, aged):
        book = QosBook(QosConfig(aging_s=1.0))
        q = ClassQueues(book, fifo=arrival_first, clock=lambda: 100.0)
        q.append(fresh)
        q.append(aged)
        return q

    fresh = SimpleNamespace(priority="standard", tenant="a",
                            qos_arrival=None, qos_enqueue_t=99.9)
    aged = SimpleNamespace(priority="batch", tenant="b",
                           qos_arrival=None, qos_enqueue_t=0.0)
    assert make(True, fresh, aged).pick() is fresh   # fifo: arrival wins
    fresh.qos_arrival = aged.qos_arrival = None
    assert make(False, fresh, aged).pick() is aged   # fair: aging fires


def test_vt_pruned_when_tenant_goes_idle():
    """A churn of distinct tenant ids must not grow the virtual-time
    ledger without bound: a tenant leaving the active set drops its
    counter when retaining it could not matter (at/below the active
    floor, or the busy interval ended), and keeps it while it still
    carries debt above the floor."""
    from types import SimpleNamespace

    from dynamo_tpu.qos.fair import QosBook

    book = QosBook(QosConfig())
    heavy = SimpleNamespace(tenant="heavy")
    light = SimpleNamespace(tenant="light")
    book.enter(heavy)
    book.enter(light)
    book.charge("heavy", "standard", 1000)
    book.charge("light", "standard", 10)
    book.leave(heavy)
    assert "heavy" in book.vt       # above the floor: debt survives idling
    book.enter(heavy)
    book.leave(light)
    assert "light" not in book.vt   # at/below the floor: pruned
    book.leave(heavy)
    assert book.vt == {}            # busy interval over: ledger empty
    for i in range(50):
        s = SimpleNamespace(tenant=f"churn-{i}")
        book.enter(s)
        book.charge(s.tenant, "standard", 5)
        book.leave(s)
    assert book.vt == {}            # id churn leaves no residue


def test_idle_tenant_banks_no_credit():
    """VTC no-banking rule: a tenant that sat idle while another was served
    re-enters at the active floor, not at zero — it gets its fair share
    going forward, not a retroactive monopoly."""
    sched = _sched(QosConfig())
    _drive(sched, [("a", "standard")], steps=200)
    vt_a = sched.qos.vt_of("a")
    assert vt_a > 0
    sched.add(_seq("b", "standard"))
    assert sched.qos.vt_of("b") == pytest.approx(vt_a)


# ------------------------------------------- priority preemption proof


def _req(tokens, osl):
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


async def _collect(eng, r, ctx=None):
    toks = []
    async for out in eng.generate(r, ctx):
        toks.extend(out.token_ids)
    return toks


N_B, ISL_B, OSL_B = 4, 64, 24
N_I, ISL_I, OSL_I = 2, 32, 16


def _mixed_engine(pool="small", **kw):
    working = (N_B * ((ISL_B + OSL_B + BS - 1) // BS)
               + N_I * ((ISL_I + OSL_I + BS - 1) // BS))
    nb = {"small": working // 2 + 1, "big": working + 8}[pool]
    defaults = dict(block_size=BS, num_blocks=nb, max_num_seqs=N_B,
                    max_num_batched_tokens=128, max_model_len=256,
                    prefill_buckets=(ISL_B,), decode_batch_buckets=(N_B,),
                    enable_prefix_caching=False)
    defaults.update(kw)
    return AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(**defaults))


def _bprompt(i):
    return [(11 * i + j) % 200 + 1 for j in range(ISL_B)]


def _iprompt(i):
    return [(7 * i + j) % 200 + 1 for j in range(ISL_I)]


async def test_priority_preemption_only_batch_yields():
    """Mixed classes under slot+KV pressure: interactive arrivals claim
    capacity from BATCH victims only, and the interactive token streams
    are bit-identical to an unloaded (big-pool, interactive-only) run —
    the swap tier absorbs the displacement, the protected class never
    notices the load."""
    eng = _mixed_engine("small")
    big = _mixed_engine("big")
    bat = [asyncio.ensure_future(_collect(
        eng, _req(_bprompt(i), OSL_B), Context(tenant="b", priority="batch")))
        for i in range(N_B)]
    # interactive arrives only once every batch sequence has computed KV:
    # any victim the arrivals displace therefore holds real progress
    for _ in range(20000):
        running = eng.scheduler.running
        if (len(running) >= N_B
                and all(s.num_computed > 0 for s in running)):
            break
        await asyncio.sleep(0.001)
    ints = [asyncio.ensure_future(_collect(
        eng, _req(_iprompt(i), OSL_I),
        Context(tenant="i", priority="interactive")))
        for i in range(N_I)]
    int_res = await asyncio.gather(*ints)
    bat_res = await asyncio.gather(*bat)

    preempts = eng.qos_stats()["preemptions"]
    assert preempts, "pressure scenario produced no preemptions"
    assert set(c for (_t, c) in preempts) == {"batch"}, preempts
    # no starvation: every batch stream still completed in full
    assert all(len(t) == OSL_B for t in bat_res)

    unloaded = await asyncio.gather(*[
        _collect(big, _req(_iprompt(i), OSL_I),
                 Context(tenant="i", priority="interactive"))
        for i in range(N_I)])
    assert int_res == unloaded  # bit-identical interactive streams
    assert all(len(t) == OSL_I for t in int_res)
    await eng.close()
    await big.close()


# --------------------------------------------- swap-in starvation guard


class _FakeSwapper:
    def __init__(self):
        self.swapped_in = []

    def swap_out(self, seq):
        return True

    def swap_status(self, seq):
        return "ready"

    def swap_in(self, seq):
        self.swapped_in.append(seq.request_id)
        return True

    def swap_drop(self, seq):
        pass


def _parked(sched, tenant, computed, t, cls="standard"):
    s = _seq(tenant, cls, isl=computed)
    s.tokens = list(s.req.token_ids)
    s.num_computed = computed
    s.parked_t = t
    s.swap = object()
    sched._stamp_qos(s)  # copies tenant/priority off ctx + qos.enter
    sched.swapped.append(s)
    return s


def test_swap_in_starvation_guard_skips_blocked_head():
    """A big head-of-line swap-in candidate that cannot reserve its blocks
    is re-parked after SWAP_IN_SKIP_AFTER failed passes so a smaller
    sequence behind it resumes; dynamo_swap_in_blocked_total counts it."""
    sched = _sched(num_blocks=8, max_num_seqs=4)  # 7 usable blocks
    swapper = _FakeSwapper()
    sched.swapper = swapper
    big = _parked(sched, "t", computed=40, t=1.0)   # needs 11 blocks: stuck
    small = _parked(sched, "t", computed=4, t=2.0)  # needs 2: resumable
    for i in range(SWAP_IN_SKIP_AFTER - 1):
        sched._swap_in_pass()
        assert swapper.swapped_in == []  # big still head, still blocked
        assert sched.swap_in_blocked_total == 0
    sched._swap_in_pass()  # attempt N: skip-ahead fires
    assert sched.swap_in_blocked_total == 1
    assert swapper.swapped_in == [small.request_id]
    assert small in sched.running
    assert big in sched.swapped  # parked, not lost


def test_swap_in_guard_crosses_classes():
    """Skip-ahead must reach WORSE classes: a sole best-class candidate
    that can never reserve its blocks is class-rank-first in
    _swap_in_candidate, so merely re-parking it (back of its own class)
    re-picks it immediately — the per-pass exclusion set lets a smaller
    batch sequence behind it resume. Aging disabled: the guard itself,
    not the aging escape hatch, must provide the progress."""
    cfg = QosConfig(aging_s=0)
    sched = _sched(qos_cfg=cfg, num_blocks=8, max_num_seqs=4)
    swapper = _FakeSwapper()
    sched.swapper = swapper
    big = _parked(sched, "vip", computed=40, t=1.0, cls="interactive")
    small = _parked(sched, "bg", computed=4, t=2.0, cls="batch")
    for _ in range(SWAP_IN_SKIP_AFTER - 1):
        sched._swap_in_pass()
        assert swapper.swapped_in == []  # interactive head still blocked
    sched._swap_in_pass()  # skip-ahead: batch seq gets its shot SAME pass
    assert sched.swap_in_blocked_total == 1
    assert swapper.swapped_in == [small.request_id]
    assert small in sched.running
    assert big in sched.swapped


def test_add_prefilled_does_not_charge_qos():
    """Disagg decode: add_prefilled attaches prompt KV the PREFILL worker
    computed (and charged on its own ledger) — charging here would debit
    the tenant's virtual counter for work this engine never did and
    double-count dynamo_tenant_served_tokens_total fleet-wide."""
    sched = _sched(num_blocks=64, max_num_seqs=4)
    s = _seq("t", "standard", isl=16)
    bt = sched.pool.allocate(16 // BS)
    sched.add_prefilled(s, bt)
    assert s in sched.running and s.num_computed == 16
    assert sched.qos.served_tokens == {}  # attach charged nothing
    assert sched.qos.vt == {}
    # locally-computed decode work afterwards still charges normally
    sched.commit_computed(s, 17)
    assert sched.qos.served_tokens == {("t", "standard"): 1}


async def test_swap_in_blocked_counter_exported():
    eng = _mixed_engine("small")
    assert "swap_in_blocked" in eng.swap_stats()
    await eng.close()


def _to_decode(sched, seq):
    sched.add(seq)
    plan = sched.plan()
    for w in plan.prefill:
        sched.commit_computed(w.seq, w.start + w.chunk)
        sched.append_token(w.seq, 5)
    assert seq in sched.running


def test_admission_preemption_no_livelock():
    """Regression: a higher-class arrival whose tenant carries MORE
    virtual time than the running batch tenant, with only the recompute
    preemption path available (no swapper). The freed slot must go to the
    arrival that forced the preemption — a re-pick would hand it back to
    the recompute-requeued victim (lower vt) and preempt it again,
    forever, hard-hanging plan()."""
    sched = _sched(max_num_seqs=2)
    b1, b2 = _seq("bat", "batch"), _seq("bat", "batch")
    sched.add(b1)
    sched.add(b2)
    plan = sched.plan()
    for w in plan.prefill:
        sched.commit_computed(w.seq, w.start + w.chunk)
    sched.qos.vt["int"] = sched.qos.vt_of("bat") + 1000.0
    i1 = _seq("int", "interactive")
    sched.add(i1)
    sched.plan()  # pre-fix: never returns
    assert i1 in sched.running
    assert sched.preempt_recompute_total == 1
    # exactly one batch victim displaced, the other still running
    assert sum(s in sched.running for s in (b1, b2)) == 1


# ------------------------------------------------------- router bias


def test_router_class_biased_cost():
    """Same cluster state, three classes: interactive routes to the idle
    worker (load dominates), batch routes to the cache-warm but loaded
    worker (overlap dominates), standard keeps the unbiased choice."""
    from dynamo_tpu.router.indexer import OverlapScores
    from dynamo_tpu.router.protocols import KvRouterConfig
    from dynamo_tpu.router.scheduler import KvScheduler

    def decide(priority):
        sched = KvScheduler(block_size=16, config=KvRouterConfig())
        sched.update_workers([1, 2])
        # worker 1: busy (active decode blocks) but holds ALL 4 prefix
        # blocks of this request; worker 2: idle, cold cache. Margins are
        # strict for every class — a tie would fall to the sampler's
        # random tie-break and flake.
        for r in range(6):
            sched.slots.add_request(f"bg-{r}", 1, [1000 + r], 256, 0)
            sched.slots.mark_prefill_completed(f"bg-{r}")
        return sched.schedule(
            "probe", isl_tokens=64, seq_hashes=[1, 2, 3, 4],
            overlaps=OverlapScores(scores={1: 4}), worker_ids=[1, 2],
            priority=priority)

    assert decide("interactive").worker_id == 2  # flees the loaded worker
    assert decide("batch").worker_id == 1        # chases the cache overlap
    d = decide(None)
    assert d.logits[1] != d.logits[2]  # unbiased cost still discriminates


# --------------------------------------------------- wire compatibility


def test_context_qos_wire_fields_roundtrip():
    ctx = Context(tenant="acme", priority="interactive")
    wire = ctx.to_wire()
    assert wire["tenant"] == "acme" and wire["priority"] == "interactive"
    back = Context.from_wire(wire)
    assert back.tenant == "acme" and back.priority == "interactive"
    child = ctx.child()
    assert child.tenant == "acme" and child.priority == "interactive"


def test_context_wire_legacy_peer_defaults():
    """A pre-QoS peer omits both fields: no KeyError, unspecified state,
    and the QoS fields stay OFF its wire dicts in return."""
    legacy = Context.from_wire({"id": "r1", "annotations": {}})
    assert legacy.tenant is None and legacy.priority is None
    assert "tenant" not in legacy.to_wire()
    assert "priority" not in legacy.to_wire()


def test_context_wire_malformed_priority_falls_back(caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger="dynamo.qos"):
        ctx = Context.from_wire({"id": "r2", "priority": "vip-gold"})
    assert ctx.priority == DEFAULT_CLASS
    assert any("vip-gold" in r.message for r in caplog.records)


async def test_legacy_context_through_engine_scheduler():
    """A worker receiving a QoS-less Context (legacy frontend) serves it
    under defaults — and a QoS-stamped Context flows through an engine
    end-to-end. Both directions of the compatibility contract."""
    eng = _mixed_engine("big")
    legacy = Context.from_wire({"id": "old-peer"})  # no tenant/priority
    toks = await _collect(eng, _req(_iprompt(0), 4), legacy)
    assert len(toks) == 4
    tagged = Context(tenant="acme", priority="interactive")
    toks2 = await _collect(eng, _req(_iprompt(0), 4), tagged)
    assert toks2 == toks  # same prompt, same greedy stream
    served = eng.qos_stats()["served_tokens"]
    assert ("default", "standard") in served  # legacy landed on defaults
    assert ("acme", "interactive") in served
    await eng.close()


# ----------------------------------------------------- frontend quotas


def _mock_request(headers=None):
    from aiohttp.test_utils import make_mocked_request

    return make_mocked_request("POST", "/v1/chat/completions",
                               headers=headers or {})


def _service(qos_cfg):
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager

    svc = HttpService(ModelManager())
    svc.qos = qos_cfg
    svc.quotas = TenantQuotas(qos_cfg)
    return svc


def test_frontend_tenant_resolution():
    cfg = QosConfig(tenants={
        "acme": TenantPolicy(priority="interactive",
                             api_keys=("sk-acme-1",))})
    svc = _service(cfg)
    # API key wins over everything
    assert svc._resolve_qos(_mock_request(
        {"Authorization": "Bearer sk-acme-1",
         "x-dynamo-tenant": "spoofed"})) == ("acme", "interactive")
    # unknown key falls through to the header
    assert svc._resolve_qos(_mock_request(
        {"Authorization": "Bearer sk-unknown",
         "x-dynamo-tenant": "self-id"})) == ("self-id", "standard")
    # explicit priority header; malformed degrades with a warning
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-tenant": "t", "x-dynamo-priority": "batch"})) \
        == ("t", "batch")
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-priority": "platinum"})) == ("default", "standard")
    # a key-protected tenant cannot be claimed by bare header (spoofing
    # would inherit its class and drain its quotas) — demoted to default;
    # a tenant configured WITHOUT keys is still header-claimable
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-tenant": "acme"})) == ("default", "standard")


def test_priority_header_cannot_escalate_without_key():
    """x-dynamo-priority may LOWER a request's class freely but may not
    raise it above the tenant's configured default unless the tenant
    authenticated with its API key — otherwise any anonymous client
    claims `interactive` and gains fair-share priority, preemption of
    other tenants' running work, and favored routing for free."""
    cfg = QosConfig(tenants={
        "corp": TenantPolicy(priority="standard", api_keys=("sk-corp",)),
        "open": TenantPolicy(priority="interactive")})
    svc = _service(cfg)
    # anonymous escalation attempt: clamped to the configured default
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-priority": "interactive"})) == ("default", "standard")
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-tenant": "adhoc",
         "x-dynamo-priority": "interactive"})) == ("adhoc", "standard")
    # downgrades are always allowed
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-priority": "batch"})) == ("default", "batch")
    # the key IS the escalation privilege
    assert svc._resolve_qos(_mock_request(
        {"Authorization": "Bearer sk-corp",
         "x-dynamo-priority": "interactive"})) == ("corp", "interactive")
    # a keyless configured tenant's default class is the operator's
    # explicit choice — claiming it (and its class) stays allowed
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-tenant": "open"})) == ("open", "interactive")


def test_malformed_priority_degrades_to_tenant_class_not_global_default():
    """A typo'd x-dynamo-priority must fall back to the TENANT's
    configured class. The global default ("standard") would silently
    ESCALATE a batch-configured tenant — and with an API key the
    escalation check is skipped entirely, so the typo ran the request a
    class above the tenant's own correctly-labeled traffic."""
    cfg = QosConfig(tenants={
        "bulk": TenantPolicy(priority="batch", api_keys=("sk-bulk",)),
        "hdr": TenantPolicy(priority="batch")})
    svc = _service(cfg)
    # key-authed: malformed header → tenant class, not "standard"
    assert svc._resolve_qos(_mock_request(
        {"Authorization": "Bearer sk-bulk",
         "x-dynamo-priority": "bacth"})) == ("bulk", "batch")
    # keyless configured tenant: same degrade rule
    assert svc._resolve_qos(_mock_request(
        {"x-dynamo-tenant": "hdr",
         "x-dynamo-priority": "bacth"})) == ("hdr", "batch")
    # a valid header still works both ways for the key-authed tenant
    assert svc._resolve_qos(_mock_request(
        {"Authorization": "Bearer sk-bulk",
         "x-dynamo-priority": "interactive"})) == ("bulk", "interactive")


def test_adhoc_tenant_cap_demotes_overflow_to_default():
    """Past DYN_QOS_MAX_TENANTS distinct self-declared ids, new names
    demote to "default": an attacker looping random x-dynamo-tenant
    values cannot grow per-tenant buckets, fairness counters, or
    /metrics label cardinality without bound. Already-admitted ids keep
    resolving."""
    svc = _service(QosConfig(max_adhoc_tenants=2))
    assert svc._resolve_qos(_mock_request({"x-dynamo-tenant": "a"}))[0] == "a"
    assert svc._resolve_qos(_mock_request({"x-dynamo-tenant": "b"}))[0] == "b"
    assert svc._resolve_qos(
        _mock_request({"x-dynamo-tenant": "c"}))[0] == "default"
    assert svc._resolve_qos(_mock_request({"x-dynamo-tenant": "a"}))[0] == "a"


def test_quota_refund_on_unserved_rejection():
    """A bucket charge whose request is then shed by the shared admission
    caps (or a pre-dispatch deadline) is refunded — otherwise a tenant
    retrying through an overloaded frontend drains its own bucket on
    requests that never ran."""
    cfg = QosConfig(tenant_rate=10.0, tenant_burst=20.0)
    quotas = TenantQuotas(cfg)
    assert quotas.admit("a", 20) is None       # bucket now empty
    quotas.refund("a", 20)                     # downstream 429: undo
    assert quotas.admit("a", 20) is None       # full charge fits again
    quotas.refund("a", 999)                    # refund caps at burst
    verdict = quotas.admit("a", 21)
    assert verdict is not None and verdict[0] == "tenant_rate"


def test_frontend_tenant_quota_429_retry_after():
    cfg = QosConfig(tenant_rate=10.0, tenant_burst=20.0)
    svc = _service(cfg)
    assert svc._qos_admission("chat", "m", "a", "standard", 20) is None
    resp = svc._qos_admission("chat", "m", "a", "standard", 20)
    assert resp is not None and resp.status == 429
    # bucket is empty: 20-token deficit at 10 tok/s -> 2 s, clamped [1,30]
    assert resp.headers["Retry-After"] == "2"
    text = svc.metrics.render()
    assert 'dynamo_tenant_rejected_total' in text
    assert 'reason="tenant_rate"' in text


def test_frontend_retry_after_from_drain_rate():
    """Satellite: the hardcoded Retry-After: 1 is gone — overload 429s
    estimate from the observed completion rate, clamped to [1, 30]."""
    svc = _service(QosConfig())
    svc.max_inflight = 1
    # cold start: no drain signal yet -> the old floor
    resp = svc._overloaded_response("chat", "m", "max_inflight")
    assert resp.headers["Retry-After"] == "1"
    # simulate 4 slow completions over ~6 s (2/3 req/s) with 3 queued
    clock = [100.0]
    svc._drain_rate = DrainRateEstimator(clock=lambda: clock[0])
    for _ in range(5):
        svc._drain_rate.note()
        clock[0] += 1.5
    svc._inflight_count = 3
    resp = svc._overloaded_response("chat", "m", "max_inflight")
    assert 1 <= int(resp.headers["Retry-After"]) <= 30
    assert resp.headers["Retry-After"] != "1"


# ------------------------------------------- two tenants, 2x oversubscribed


async def test_qos_bench_smoke():
    """Two tenants share one engine whose KV pool holds about half the
    combined working set and whose seq slots hold half the offered
    concurrency: a batch-class tenant floods first, then an interactive
    tenant arrives. The structural guarantees: the batch class completes in
    full in every pass (no starvation) and only batch-class sequences are
    preempted."""
    import numpy as np

    cfg = ModelConfig.tiny()
    n_i, isl_i, osl_i = 8, 32, 16
    # batch OSL long enough that the swap preemptions the interactive wave
    # triggers land on sustained decode, not a prefill sprint
    n_b, isl_b, osl_b = 8, 128, 64
    slots = 8  # 16 offered seqs -> 2x compute oversubscription
    working = (n_b * ((isl_b + osl_b + BS - 1) // BS)
               + n_i * ((isl_i + osl_i + BS - 1) // BS))
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=BS, num_blocks=working // 2 + 1, max_num_seqs=slots,
        max_num_batched_tokens=2 * isl_b, max_model_len=2 * (isl_b + osl_b),
        prefill_buckets=(isl_b,), decode_batch_buckets=(slots,),
        enable_prefix_caching=False, qos_scheduling=True))
    rng = np.random.default_rng(23)
    int_prompts = [rng.integers(1, cfg.vocab_size, isl_i).tolist()
                   for _ in range(n_i)]
    bat_prompts = [rng.integers(1, cfg.vocab_size, isl_b).tolist()
                   for _ in range(n_b)]

    async def one(tokens, osl, tenant, cls):
        r = PreprocessedRequest(
            model="m", token_ids=list(tokens),
            stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        n = 0
        async for out in eng.generate(r, Context(tenant=tenant,
                                                 priority=cls)):
            n += len(out.token_ids)
        return n

    async def mixed():
        """Batch floods first; interactive arrives once batch occupies the
        engine. Returns the batch tenant's completed tokens."""
        bat = [asyncio.ensure_future(one(p, osl_b, "tenant-bat", "batch"))
               for p in bat_prompts]
        for _ in range(20000):
            if (len(eng.scheduler.running) >= min(slots, n_b) - 1
                    and any(s.num_computed > 0
                            for s in eng.scheduler.running)):
                break
            await asyncio.sleep(0.001)
        ints = [asyncio.ensure_future(
            one(p, osl_i, "tenant-int", "interactive")) for p in int_prompts]
        await asyncio.gather(*ints)
        return sum(await asyncio.gather(*bat))

    try:
        for _ in range(2):
            assert await mixed() == n_b * osl_b
        preempted = {cls for (_tenant, cls), n
                     in eng.qos_stats()["preemptions"].items() if n}
        assert preempted <= {"batch"}, preempted
    finally:
        await eng.close()
