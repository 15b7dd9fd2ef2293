"""Observability spine: span recorder, trace propagation/stitching, SLO
histograms, Prometheus exposition correctness, and the full-stack smoke
(one mock request → complete stitched trace + /metrics series)."""

import contextvars
import json

import pytest

from dynamo_tpu.observability import (
    Span,
    Tracer,
    fetch_trace,
    parse_traceparent,
    serve_traces,
    stitch,
)
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.metrics import (
    Histogram,
    MetricsRegistry,
    _fmt_labels,
    render_registries,
)

pytestmark = pytest.mark.anyio


# ------------------------------------------------- Prometheus exposition


def test_label_value_escaping():
    """Backslash, double-quote, and newline in label values must be escaped
    or the exposition format is corrupt (satellite fix)."""
    out = _fmt_labels({"model": 'a"b\\c\nd'})
    assert out == '{model="a\\"b\\\\c\\nd"}'
    # escaped output is a single physical line
    assert "\n" not in out

    reg = MetricsRegistry()
    reg.counter("reqs", "requests").inc(model='we"ird\nname\\x')
    text = reg.render()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("dynamo_reqs{"))
    assert '\\"' in line and "\\n" in line and "\\\\" in line


def test_histogram_bucket_math():
    """Bucket counts are CUMULATIVE, +Inf equals the total count, and sum
    accumulates the raw values (satellite test coverage)."""
    h = Histogram("dynamo_t", "t", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = h.render()
    lines = dict(
        ln.rsplit(" ", 1) for ln in text.splitlines()
        if not ln.startswith("#"))
    assert lines['dynamo_t_bucket{le="0.1"}'] == "1"
    assert lines['dynamo_t_bucket{le="1.0"}'] == "3"
    assert lines['dynamo_t_bucket{le="10.0"}'] == "4"
    assert lines['dynamo_t_bucket{le="+Inf"}'] == "5"
    assert lines["dynamo_t_count"] == "5"
    assert abs(float(lines["dynamo_t_sum"]) - 56.05) < 1e-9

    # labeled series keep independent bucket vectors
    h2 = Histogram("dynamo_p", "p", buckets=(1.0,))
    h2.observe(0.5, phase="a")
    h2.observe(2.0, phase="b")
    t2 = h2.render()
    assert 'dynamo_p_bucket{le="1.0",phase="a"} 1' in t2
    assert 'dynamo_p_bucket{le="1.0",phase="b"} 0' in t2


def test_uptime_help_and_merged_registries():
    """dynamo_uptime_seconds carries a # HELP line, and rendering two
    registries together emits each # TYPE/# HELP header (and the unlabeled
    uptime sample) exactly once (satellite fixes)."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("reqs", "requests").inc(route="x")
    b.counter("reqs", "requests").inc(route="y")
    b.histogram("ttft_seconds", "ttft").observe(0.1)

    single = a.render()
    assert "# HELP dynamo_uptime_seconds" in single

    merged = render_registries(a, b)
    assert merged.count("# TYPE dynamo_uptime_seconds gauge") == 1
    assert merged.count("# TYPE dynamo_reqs counter") == 1
    assert merged.count("# HELP dynamo_reqs") == 1
    # both registries' labeled series survive the merge
    assert 'dynamo_reqs{route="x"}' in merged
    assert 'dynamo_reqs{route="y"}' in merged
    # exactly one unlabeled uptime sample
    ups = [ln for ln in merged.splitlines()
           if ln.startswith("dynamo_uptime_seconds ")]
    assert len(ups) == 1
    assert "dynamo_ttft_seconds" in merged


def test_merged_registries_duplicate_unlabeled_histogram():
    """Two registries sharing an unlabeled histogram must not emit
    duplicate _bucket/_sum/_count series (Prometheus rejects the scrape)."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("ttft_seconds", "t", buckets=(1.0,)).observe(0.5)
    b.histogram("ttft_seconds", "t", buckets=(1.0,)).observe(0.7)
    merged = render_registries(a, b)
    assert merged.count('dynamo_ttft_seconds_bucket{le="1.0"}') == 1
    assert len([ln for ln in merged.splitlines()
                if ln.startswith("dynamo_ttft_seconds_sum")]) == 1
    # labeled histograms from a later registry still merge through
    b2 = MetricsRegistry()
    b2.histogram("phase_seconds", "p", buckets=(1.0,)).observe(0.5, phase="x")
    merged2 = render_registries(a, b2)
    assert 'phase="x"' in merged2


def test_malformed_traceparent_still_traces():
    """A malformed client traceparent is replaced (W3C ignore-invalid), so
    tracing/SLO recording survives instead of silently no-opping."""
    ctx = Context(traceparent="garbage")
    tp = ctx.ensure_traceparent()
    assert parse_traceparent(tp) is not None
    assert ctx.traceparent_synthesized  # the frontend keys root adoption on this
    tracer = Tracer(service="t", capacity=8)
    with tracer.span("http.request", ctx,
                     adopt_wire_span=ctx.traceparent_synthesized) as root:
        pass
    assert len(tracer.all_spans()) == 1
    assert root.parent_span_id is None  # no phantom parent
    # a VALID inbound traceparent is preserved and stays the remote parent
    good = Context(traceparent="00-" + "a" * 32 + "-" + "b" * 16 + "-01")
    good.ensure_traceparent()
    assert not good.traceparent_synthesized
    with tracer.span("http.request", good,
                     adopt_wire_span=good.traceparent_synthesized) as r2:
        pass
    assert r2.trace_id == "a" * 32 and r2.parent_span_id == "b" * 16


def test_future_version_traceparent_accepted():
    """W3C: parsers must accept the first four fields of higher-version
    traceparent headers (which may carry extra dash-separated fields)."""
    tp = "cc-" + "a" * 32 + "-" + "b" * 16 + "-01-extrafield"
    ctx = Context(traceparent=tp)
    assert ctx.ensure_traceparent() == tp  # preserved, not replaced
    assert not ctx.traceparent_synthesized
    assert parse_traceparent(tp) == ("a" * 32, "b" * 16)
    # the next hop rewrites to the 4-field form we understand
    hop = ctx.child_traceparent()
    parts = hop.split("-")
    assert len(parts) == 4 and parts[1] == "a" * 32 and parts[2] != "b" * 16


def test_rpc_hop_spans_stay_out_of_histograms():
    """rpc.send markers (start==end) are stored for stitching but excluded
    from dynamo_phase_seconds — an always-zero phase is dashboard noise."""
    tracer = Tracer(service="t", capacity=8)
    ctx = Context()
    ctx.ensure_traceparent()
    hop = tracer.record_hop(ctx, ctx.child_traceparent())
    assert any(s.span_id == hop.span_id for s in tracer.all_spans())
    assert 'phase="rpc.send"' not in tracer.metrics.render()


async def test_metrics_aggregator_counter_types():
    """kv_blocks_{stored,removed}_total render as counters, not gauges
    (satellite fix in metrics/main.py)."""
    from dynamo_tpu.metrics.main import MetricsService
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create()
    try:
        svc = MetricsService(rt)
        svc.kv_stored, svc.kv_removed = 7, 3
        text = svc.render(prefill_queue_depth=2)
        assert "# TYPE dynamo_kv_blocks_stored_total counter" in text
        assert "# TYPE dynamo_kv_blocks_removed_total counter" in text
        assert "dynamo_kv_blocks_stored_total 7" in text
        # non-monotonic series stay gauges
        assert "# TYPE dynamo_prefill_queue_depth gauge" in text
    finally:
        await rt.shutdown()


# ---------------------------------------------------- tracer + propagation


def test_traceparent_roundtrip_and_span_parenting():
    """Trace ids survive to_wire/from_wire, and the rpc.send hop span
    recorded by the sender stitches the receiver's spans back to the
    sender's chain (frontend→worker hop, simulated)."""
    frontend = Tracer(service="frontend", capacity=64)
    worker = Tracer(service="worker", capacity=64)

    ctx = Context()
    with frontend.span("http.request", ctx) as root:
        assert root.trace_id == parse_traceparent(ctx.traceparent)[0]
        ctx_wire = ctx.to_wire()
        hop = frontend.record_hop(ctx, ctx_wire["traceparent"])
        # wire round-trip: same trace id, fresh span id
        w_trace, w_span = parse_traceparent(ctx_wire["traceparent"])
        assert w_trace == root.trace_id and w_span != root.span_id
        assert hop.span_id == w_span
        assert hop.parent_span_id == root.span_id

        # "worker process": fresh contextvars (no inherited CURRENT_SPAN)
        wctx = Context.from_wire(ctx_wire)

        def worker_side():
            with worker.span("worker.handle", wctx) as sp:
                pass
            return sp

        wspan = contextvars.Context().run(worker_side)
    assert wspan.trace_id == root.trace_id
    assert wspan.parent_span_id == hop.span_id  # stitches through the hop

    # the full set stitches into one rooted tree with no orphans
    spans = [s.to_dict() for s in
             frontend.spans_for(ctx.id) + worker.spans_for(ctx.id)]
    assert {s["name"] for s in spans} == {"http.request", "rpc.send",
                                          "worker.handle"}
    tree = stitch(spans)
    assert [t["name"] for t in tree] == ["http.request", "rpc.send",
                                         "worker.handle"]
    assert [t["depth"] for t in tree] == [0, 1, 2]


def test_tracer_same_task_nesting_and_noop():
    tracer = Tracer(service="t", capacity=8)
    ctx = Context()
    with tracer.span("outer", ctx) as outer:
        with tracer.span("inner", ctx) as inner:
            inner.set(k=1)
        assert inner.parent_span_id == outer.span_id
    # ring buffer bound: capacity 8 keeps only the newest 8
    for i in range(20):
        tracer.record("x", ctx, start=float(i), end=float(i))
    assert len(tracer.all_spans()) == 8

    # trace-less contexts no-op instead of raising
    class NullCtx:
        id = "local"
        cancelled = False

    with tracer.span("nope", NullCtx()) as sp:
        sp.set(a=1)
        sp.status = "error"  # noop spans swallow attribute writes
    assert all(s.name != "nope" for s in tracer.all_spans())


def test_span_histograms_and_jsonl_export(tmp_path):
    """Span end feeds dynamo_phase_seconds{phase=...} (+ the per-name SLO
    histograms), and the buffer exports as JSONL."""
    tracer = Tracer(service="t", capacity=32)
    ctx = Context()
    tracer.record("ttft", ctx, start=100.0, end=100.5)
    tracer.record("http.request", ctx, start=100.0, end=101.0)
    text = tracer.metrics.render()
    assert 'dynamo_phase_seconds_bucket{le="0.5",phase="ttft"} 1' in text
    assert "dynamo_ttft_seconds_count 1" in text
    assert "dynamo_e2e_seconds_count 1" in text
    assert "dynamo_itl_seconds" in text  # pre-created, present when empty

    path = tmp_path / "spans.jsonl"
    n = tracer.export_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert n == len(lines) == 2
    assert {d["name"] for d in lines} == {"ttft", "http.request"}
    assert Span.from_dict(lines[0]).trace_id == lines[0]["trace_id"]


async def test_trace_collector_over_control_plane():
    """serve_traces registers under the primary lease; fetch_trace fans out
    and merges (the transport behind /v1/traces and dynctl trace)."""
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create()
    try:
        tracer = Tracer(service="workerA", capacity=32)
        ctx = Context(id="req-1")
        tracer.record("engine.ttft", ctx, start=1.0, end=1.2)
        tracer.record("engine.decode", ctx, start=1.2, end=2.0)
        handle = await serve_traces(rt, tracer)

        spans = await fetch_trace(rt.plane, "req-1")
        assert {s["name"] for s in spans} == {"engine.ttft", "engine.decode"}
        assert spans[0]["start"] <= spans[1]["start"]
        assert await fetch_trace(rt.plane, "no-such-request") == []

        await handle.stop()
        assert await fetch_trace(rt.plane, "req-1") == []
    finally:
        await rt.shutdown()


# ------------------------------------------------------ end-to-end smoke


#: span names one mock request through the full stack must produce
OBSERVE_PHASES = (
    "http.request", "preprocess.tokenize", "router.schedule",
    "worker.handle", "engine.ttft", "engine.decode", "ttft", "itl",
)
#: Prometheus series /metrics must expose out of the box
OBSERVE_SERIES = (
    "dynamo_ttft_seconds", "dynamo_itl_seconds", "dynamo_e2e_seconds",
    "dynamo_phase_seconds",
)


async def test_observe_smoke_full_stack():
    """One mock request through the full serving stack yields a complete
    stitched trace (every named phase, TTFT and ITL among them, no span
    orphaned) via /v1/traces/{request_id}, and /metrics exposes the SLO
    histograms."""
    import asyncio

    import aiohttp

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.mocker.engine import MockEngineArgs
    from dynamo_tpu.mocker.main import run_mocker
    from dynamo_tpu.observability import configure_tracer
    from dynamo_tpu.runtime import DistributedRuntime

    configure_tracer(service="observe")  # fresh buffer: hermetic assertions
    rt = await DistributedRuntime.create()
    # setup INSIDE the try: a failing start must not leak engine loops /
    # watcher tasks into the rest of the suite
    engines, handles = [], []
    watcher = service = None
    try:
        args = MockEngineArgs(vocab_size=make_test_tokenizer().vocab_size,
                              block_size=4, num_gpu_blocks=128,
                              speedup_ratio=20.0)
        engines, handles = await run_mocker(rt, "observe", args)
        manager = ModelManager()
        watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
        service = HttpService(manager, port=0, runtime=rt)
        await service.start()
        for _ in range(200):
            if manager.list_models():
                break
            await asyncio.sleep(0.05)
        else:
            raise RuntimeError("model never appeared in discovery")

        rid = "observe-smoke-request"
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as http:
            async with http.post(
                    f"{base}/v1/completions",
                    json={"model": "observe", "prompt": "hello tokens stream",
                          "max_tokens": 8, "stream": True,
                          "ignore_eos": True},
                    headers={"x-request-id": rid}) as resp:
                assert resp.status == 200, await resp.text()
                async for _ in resp.content:
                    pass
            async with http.get(f"{base}/v1/traces/{rid}") as resp:
                assert resp.status == 200, await resp.text()
                trace = await resp.json()
            async with http.get(f"{base}/metrics") as resp:
                assert resp.status == 200
                metrics_text = await resp.text()
    finally:
        if service is not None:
            await service.stop()
        if watcher is not None:
            await watcher.stop()
        for h in handles:
            await h.stop(graceful=False)
        for e in engines:
            await e.stop()
        await rt.shutdown()

    phases = set(trace["phases"])
    assert len(phases) >= 6
    assert not [p for p in OBSERVE_PHASES if p not in phases], sorted(phases)
    assert not [s for s in OBSERVE_SERIES if s not in metrics_text]
    # every span must stitch: a recorded parent id that is absent from the
    # trace means a broken hop in the parenting chain
    ids = {s["span_id"] for s in trace["spans"]}
    assert not [s["name"] for s in trace["spans"]
                if s.get("parent_span_id") and s["parent_span_id"] not in ids]
