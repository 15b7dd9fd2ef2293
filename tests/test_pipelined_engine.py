"""Pipelined decode loop, coalesced emission, and bucket warmup (ISSUE 2).

Covers the decode-critical-path rework: the depth-2 pipelined engine loop
must be greedy/seed-invariant vs the serial loop, dispatch step N+1 before
step N's host emission, survive mid-flight cancellation and step
exceptions, keep per-sequence token order; coalesced SSE chunks must
re-split into valid OpenAI deltas; the AOT warmup pass must compile each
configured bucket exactly once; the corked StreamSender must deliver every
frame with at most one drain per high-water mark.
"""

import asyncio
import json

import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


def tiny_engine(**kw) -> AsyncJaxEngine:
    cfg = ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=8,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64),
                    decode_batch_buckets=(1, 2, 4, 8))
    defaults.update(kw)
    return AsyncJaxEngine(cfg, EngineArgs(**defaults))


def req(tokens, max_tokens=8, **sampling) -> PreprocessedRequest:
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(**sampling),
    )


async def collect(eng, r):
    toks, reason = [], None
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            reason = out.finish_reason
    return toks, reason


# --------------------------------------------------------- pipelined decode


async def test_pipelined_matches_serial_greedy_and_seeded():
    """The pipelined loop is an execution-order optimization ONLY: tokens
    (greedy AND seeded sampling) must match the serial loop exactly, per
    sequence, in order."""
    prompts = [list(range(1, 20)), list(range(30, 45)), list(range(7, 18))]
    for sampling in ({}, dict(temperature=0.8, seed=7)):
        e_on = tiny_engine()
        e_off = tiny_engine(pipeline_decode=False)
        a = await asyncio.gather(
            *[collect(e_on, req(p, max_tokens=12, **sampling))
              for p in prompts])
        b = await asyncio.gather(
            *[collect(e_off, req(p, max_tokens=12, **sampling))
              for p in prompts])
        assert a == b
        assert all(len(t) == 12 for t, _ in a)
        assert e_on.pipelined_steps > 0, "pipelined path never engaged"
        assert e_off.pipelined_steps == 0
        await e_on.close()
        await e_off.close()


async def test_pipeline_dispatch_does_not_wait_on_commit():
    """The acceptance-criterion ordering proof: step N+1's dispatch happens
    BEFORE step N's commit/emission, and commits land in dispatch order
    (per-sequence token order preserved)."""
    eng = tiny_engine()
    events = []
    orig_d = eng._dispatch_decode_step
    orig_c = eng._commit_decode_step

    def d(seqs, feed=None):
        h = orig_d(seqs, feed=feed)
        if h is not None:
            events.append(("dispatch", id(h)))
        return h

    async def c(h):
        events.append(("commit", id(h)))
        return await orig_c(h)

    eng._dispatch_decode_step = d
    eng._commit_decode_step = c
    toks, reason = await collect(eng, req(range(1, 10), max_tokens=8))
    assert len(toks) == 8 and reason == FinishReason.LENGTH
    dispatches = [i for i, (k, _) in enumerate(events) if k == "dispatch"]
    commits = [i for i, (k, _) in enumerate(events) if k == "commit"]
    assert len(dispatches) >= 2 and commits
    # the second dispatch was issued before the FIRST commit completed:
    # step N's host copy + emission overlapped step N+1's device time
    assert dispatches[1] < commits[0]
    # every in-flight step commits, in dispatch order
    assert ([h for k, h in events if k == "commit"]
            == [h for k, h in events if k == "dispatch"])
    await eng.close()


async def test_cancellation_mid_pipeline():
    """Cancelling one sequence mid-pipelined-flight drains the pipeline,
    reaps the sequence, and leaves the other stream running to completion."""
    eng = tiny_engine()

    class Ctx:
        cancelled = False
        id = "cancel-me"

    ctx = Ctx()

    async def consume_then_cancel():
        n = 0
        async for out in eng.generate(req(range(1, 10), max_tokens=500), ctx):
            n += len(out.token_ids)
            if n >= 4:
                ctx.cancelled = True
        return n

    n1, (toks2, reason2) = await asyncio.wait_for(
        asyncio.gather(consume_then_cancel(),
                       collect(eng, req(range(30, 40), max_tokens=12))),
        timeout=120)
    assert n1 < 500  # cancelled stream actually stopped
    assert len(toks2) == 12 and reason2 == FinishReason.LENGTH
    await eng.close()


async def test_step_exception_fails_all_inflight_then_recovers():
    """A step failure with a pipelined dispatch in flight must fail EVERY
    in-flight sequence (no hung consumers, no unretrieved task errors) and
    leave the engine loop serving subsequent requests."""
    eng = tiny_engine()
    calls = {"n": 0}

    def wrap(real):
        def boom(*a):
            calls["n"] += 1
            if calls["n"] == 4:  # past prefill + first pipelined dispatches
                raise RuntimeError("injected step failure")
            return real(*a)
        return boom

    # wrap both ragged step entry points (mixed + pipelined decode-only)
    eng.ragged_fn = wrap(eng.ragged_fn)
    eng.ragged_dec_fn = wrap(eng.ragged_dec_fn)
    results = await asyncio.gather(
        collect(eng, req(range(1, 12), max_tokens=50)),
        collect(eng, req(range(20, 33), max_tokens=50)))
    assert all(r == FinishReason.ERROR for _, r in results)
    # the loop survived: a fresh request completes normally
    toks, reason = await collect(eng, req(range(40, 50), max_tokens=5))
    assert len(toks) == 5 and reason == FinishReason.LENGTH
    await eng.close()


async def test_pipeline_respects_feature_gates():
    """Requests needing host-side logit work (logprobs, logit_bias) must
    fall back to the serial path — and still produce correct streams."""
    eng = tiny_engine()
    r = req(range(1, 12), max_tokens=6)
    r.output_options.logprobs = 2
    toks, reason = await collect(eng, r)
    assert len(toks) == 6 and reason == FinishReason.LENGTH
    assert eng.pipelined_steps == 0
    await eng.close()


# ------------------------------------------------------- event-driven wakeup


async def test_block_free_sets_engine_wake():
    """The memory-starved engine loop parks on _wake; a BlockPool release
    must set it (the event-driven replacement for the 5 ms poll)."""
    eng = tiny_engine()
    assert eng.pool.on_freed is not None
    ids = eng.pool.allocate(2)
    eng._wake.clear()
    eng.pool.release(ids)
    assert eng._wake.is_set()
    await eng.close()


async def test_starved_engine_makes_progress():
    """With far fewer blocks than the concurrent demand, sequences must
    still all complete via finish→release→wake (no poll to lean on)."""
    eng = tiny_engine(num_blocks=14, max_num_seqs=4,
                      max_num_batched_tokens=16, max_model_len=64,
                      prefill_buckets=(8, 16), decode_batch_buckets=(1, 2, 4))

    async def one(seed):
        prompt = [1 + (seed * 11 + i) % 200 for i in range(12)]
        return await collect(eng, req(prompt, max_tokens=6))

    results = await asyncio.wait_for(
        asyncio.gather(*(one(i) for i in range(4))), timeout=240)
    assert all(len(t) == 6 for t, _ in results)
    await eng.close()


# ------------------------------------------------------------ bucket warmup


async def test_warmup_compiles_each_bucket_exactly_once():
    """The AOT warmup pass dispatches exactly one dummy step per ragged
    signature (token bucket × variant), and a real request inside the
    warmed envelope adds NO new step signature (its compiles were all
    paid up front)."""
    eng = tiny_engine()
    sigs = []

    def wrap(kind, real):
        def counting(params, ints5, rows3, gr, bt, k, v):
            sigs.append((kind, tuple(ints5.shape)))
            return real(params, ints5, rows3, gr, bt, k, v)
        return counting

    eng.ragged_fn = wrap("ragged", eng.ragged_fn)
    eng.ragged_dec_fn = wrap("ragged_dec", eng.ragged_dec_fn)
    rep = await eng.warmup()
    buckets = list(eng.args.ragged_token_buckets)
    # both variants trace every configured token bucket, exactly once
    for kind in ("ragged", "ragged_dec"):
        assert sorted(t for k, t, *_ in rep["ragged"] if k == kind) \
            == buckets
    assert len(sigs) == len(set(sigs)), "duplicate warmup dispatch"
    warm = set(sigs)
    # prompt 10 + 4 generated = 14 tokens: inside the warmed envelope
    toks, _ = await collect(eng, req(range(1, 11), max_tokens=4))
    assert len(toks) == 4
    assert set(sigs) == warm, f"post-warmup compile: {set(sigs) - warm}"
    await eng.close()


# --------------------------------------------------- coalesced token streams


async def test_coalesced_sse_resplits_into_valid_openai_deltas():
    """multi_step_decode engine → per-step batched LLMEngineOutputs →
    batched SSE writes: every `data:` record must still parse as a valid
    OpenAI completion chunk, and the re-assembled text must equal the
    non-streaming result. Fewer chunks than tokens proves coalescing."""
    import aiohttp

    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
    from dynamo_tpu.runtime import DistributedRuntime
    from tests.hf_fixture import write_wordlevel_tokenizer_dir
    import tempfile

    tmp = tempfile.mkdtemp(prefix="coalesce-tk-")
    cfg = ModelConfig.tiny()
    write_wordlevel_tokenizer_dir(tmp, cfg.vocab_size)

    rt = await DistributedRuntime.create()
    eng = tiny_engine(multi_step_decode=4)
    backend = rt.namespace("dynamo").component("backend")
    handle = await backend.endpoint("generate").serve_endpoint(
        DecodeWorkerHandler(eng).generate)
    card = ModelDeploymentCard(display_name="coalesce", kv_cache_block_size=4,
                               eos_token_ids=[], tokenizer_ref=tmp,
                               context_length=256)
    await register_llm(rt, backend.endpoint("generate"), card)
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    service = HttpService(manager, port=0)
    await service.start()
    try:
        for _ in range(100):
            if manager.list_models():
                break
            await asyncio.sleep(0.05)
        base = f"http://127.0.0.1:{service.port}/v1/completions"
        body = {"model": "coalesce", "prompt": list(range(1, 12)),
                "max_tokens": 12, "ignore_eos": True, "temperature": 0.0}
        async with aiohttp.ClientSession() as http:
            chunks = []
            stream_body = dict(body, stream=True,
                               stream_options={"include_usage": True})
            async with http.post(base, json=stream_body) as resp:
                assert resp.status == 200, await resp.text()
                async for raw in resp.content:
                    line = raw.decode()
                    if not line.startswith("data: "):
                        continue
                    if line.startswith("data: [DONE]"):
                        break
                    chunks.append(json.loads(line[6:]))
            async with http.post(base, json=body) as resp:
                assert resp.status == 200, await resp.text()
                full = await resp.json()
        # every chunk is a well-formed completion delta
        for c in chunks:
            assert c["object"] == "text_completion" and c["choices"]
            assert isinstance(c["choices"][0].get("text", ""), str)
        streamed = "".join(c["choices"][0].get("text") or "" for c in chunks)
        assert streamed == full["choices"][0]["text"]
        usage = next(c["usage"] for c in chunks if c.get("usage"))
        assert usage["completion_tokens"] == 12
        # 12 tokens arrived in K-token bursts: strictly fewer chunks
        assert len(chunks) < 12
    finally:
        await service.stop()
        await watcher.stop()
        await handle.stop(graceful=False)
        await eng.close()
        await rt.shutdown()


async def test_pump_handler_terminates_on_cancel_midstream():
    """A handler still yielding items after ctx.cancel() must not deadlock
    the worker pump: the stream terminates with a sentinel either way
    (regression: the batched pump once skipped the end marker on cancel)."""
    from dynamo_tpu.runtime.component import _pump_handler
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.response_plane import (
        StreamSender, make_local_stream,
    )

    ctx = Context()
    info, receiver, q = make_local_stream(ctx)
    sender = StreamSender.local(q)

    async def handler(request, c):
        yield {"a": 1}
        ctx.cancel()
        yield {"a": 2}
        yield {"a": 3}

    await asyncio.wait_for(_pump_handler(handler, {}, ctx, sender), timeout=5)
    # the receiver's iteration ENDS (complete sentinel arrived) instead of
    # hanging on a never-closed stream
    got = await asyncio.wait_for(
        asyncio.ensure_future(_drain_receiver(receiver)), timeout=5)
    assert all(item["a"] in (1, 2, 3) for item in got)


async def _drain_receiver(receiver):
    return [item async for item in receiver]


async def test_batched_stream_helper():
    """_batched coalesces already-queued items into one list and relays
    producer exceptions after flushing buffered items."""
    from dynamo_tpu.frontend.http import _batched

    async def gen():
        yield 1
        yield 2
        await asyncio.sleep(0.01)
        yield 3

    batches = [b async for b in _batched(gen())]
    assert batches[0] == [1, 2]  # back-to-back items coalesce
    assert [x for b in batches for x in b] == [1, 2, 3]

    async def bad():
        yield 1
        raise ValueError("boom")

    seen = []
    with pytest.raises(ValueError):
        async for b in _batched(bad()):
            seen.extend(b)
    assert seen == [1]


async def test_stream_sender_cork_and_send_many():
    """Corked sends: 100 small frames cost zero drains (under the high
    water mark), arrive intact and in order; flush() pays exactly one."""
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.response_plane import (
        ResponseStreamServer, StreamSender,
    )

    server = ResponseStreamServer(host="127.0.0.1")
    await server.start()
    ctx = Context()
    info, receiver = server.register_stream(ctx)
    sender = await StreamSender.connect(info, ctx)
    drains = {"n": 0}
    real_drain = sender._writer.drain

    async def counting_drain():
        drains["n"] += 1
        await real_drain()

    sender._writer.drain = counting_drain
    try:
        for i in range(50):
            await sender.send({"i": i})
        await sender.send_many([{"i": i} for i in range(50, 100)])
        assert drains["n"] == 0, "per-frame drain resurrected"
        await sender.flush()
        assert drains["n"] == 1
        await sender.flush()  # nothing unflushed: no extra drain
        assert drains["n"] == 1
        await sender.complete()
        got = [item async for item in receiver]
        assert got == [{"i": i} for i in range(100)]
    finally:
        await server.stop()


async def test_stream_sender_high_water_drains():
    """Past SEND_HIGH_WATER unflushed bytes, send() pays a drain — the
    backpressure bound for slow requesters."""
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.response_plane import (
        ResponseStreamServer, StreamSender,
    )

    server = ResponseStreamServer(host="127.0.0.1")
    await server.start()
    ctx = Context()
    info, receiver = server.register_stream(ctx)
    sender = await StreamSender.connect(info, ctx)
    drains = {"n": 0}
    real_drain = sender._writer.drain

    async def counting_drain():
        drains["n"] += 1
        await real_drain()

    sender._writer.drain = counting_drain
    try:
        payload = {"blob": "x" * (StreamSender.SEND_HIGH_WATER // 4)}
        for _ in range(8):
            await sender.send(payload)
        assert drains["n"] >= 1
        await sender.complete()
        got = [item async for item in receiver]
        assert len(got) == 8
    finally:
        await server.stop()
