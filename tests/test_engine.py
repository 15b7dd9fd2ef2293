"""JAX engine tests: determinism, chunked prefill, prefix cache, batching,
KV events, sampling — all on the virtual CPU mesh (conftest.py).

Mirrors the reference's engine-behavior test intent (ref:
tests/kvbm/test_determinism.py — identical outputs with/without cache reuse;
mocker scheduler tests — admission/chunking semantics).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.cache import BlockPool
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.tokens import KV_HASH_SEED, compute_block_hash_for_seq

pytestmark = pytest.mark.anyio


def tiny_engine(**kw) -> AsyncJaxEngine:
    cfg = ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=8,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64), decode_batch_buckets=(1, 2, 4, 8))
    defaults.update(kw)
    args = EngineArgs(**defaults)
    events = []
    eng = AsyncJaxEngine(cfg, args, event_cb=events.append)
    eng.test_events = events
    return eng


def req(tokens, max_tokens=8, **sampling) -> PreprocessedRequest:
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**sampling),
    )


async def collect(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            reason = out.finish_reason
    return toks, reason


async def test_greedy_determinism():
    eng = tiny_engine()
    prompt = list(range(1, 20))
    t1, r1 = await collect(eng, req(prompt))
    t2, r2 = await collect(eng, req(prompt))
    assert t1 == t2
    assert len(t1) == 8
    assert r1 == r2 == FinishReason.LENGTH
    await eng.close()


async def test_chunked_prefill_equivalence():
    prompt = list(range(1, 50))  # 49 tokens, will be chunked at budget 16
    eng_small = tiny_engine(max_num_batched_tokens=16)
    t_small, _ = await collect(eng_small, req(prompt))
    await eng_small.close()

    eng_big = tiny_engine(max_num_batched_tokens=64)
    t_big, _ = await collect(eng_big, req(prompt))
    await eng_big.close()
    assert t_small == t_big


async def test_prefix_cache_reuse_and_consistency():
    eng = tiny_engine()
    prompt = list(range(1, 26))  # 25 tokens = 6 full blocks + 1
    t1, _ = await collect(eng, req(prompt))
    assert eng.scheduler.prefix_hit_tokens == 0
    t2, _ = await collect(eng, req(prompt))
    # second run must reuse the 6 full prompt blocks and match exactly
    assert eng.scheduler.prefix_hit_tokens == 24
    assert t1 == t2
    await eng.close()


@pytest.mark.slow
async def test_concurrent_batch_matches_solo():
    prompts = [list(range(1, 10)), list(range(5, 30)), list(range(40, 48))]
    eng = tiny_engine(enable_prefix_caching=False)
    solo = []
    for p in prompts:
        t, _ = await collect(eng, req(p))
        solo.append(t)
    await eng.close()

    eng2 = tiny_engine(enable_prefix_caching=False)
    results = await asyncio.gather(*(collect(eng2, req(p)) for p in prompts))
    await eng2.close()
    for (toks, _), expect in zip(results, solo):
        assert toks == expect


async def test_kv_events_hash_domain():
    """Stored events must carry the frontend's salted-xxh3 hash chain."""
    eng = tiny_engine()
    prompt = list(range(1, 14))  # 13 tokens = 3 full blocks of 4
    toks, _ = await collect(eng, req(prompt, max_tokens=4))
    stored = [e for e in eng.test_events if e.stored_blocks]
    assert stored
    all_blocks = [b for e in stored for b in e.stored_blocks]
    # full sequence entering the cache: 13 prompt + 3 computed gen tokens
    # (the 4th sampled token never gets a forward pass) = 16 = 4 blocks
    full_seq = prompt + toks[:3]
    expect_local = compute_block_hash_for_seq(full_seq, 4, KV_HASH_SEED)
    got_local = [b.tokens_hash for b in all_blocks]
    assert got_local == expect_local
    await eng.close()


async def test_sampling_seeded_determinism():
    eng = tiny_engine()
    prompt = list(range(1, 12))
    r1 = req(prompt, temperature=0.9, top_k=20, seed=42)
    r2 = req(prompt, temperature=0.9, top_k=20, seed=42)
    r3 = req(prompt, temperature=0.9, top_k=20, seed=7)
    t1, _ = await collect(eng, r1)
    t2, _ = await collect(eng, r2)
    t3, _ = await collect(eng, r3)
    assert t1 == t2
    assert t3 != t1  # overwhelmingly likely
    await eng.close()


async def test_eviction_emits_removed_events():
    # tiny pool: force eviction pressure
    eng = tiny_engine(num_blocks=24, max_model_len=64, max_num_seqs=2)
    for base in range(0, 5):
        p = list(range(base * 7 + 1, base * 7 + 30))
        await collect(eng, req(p, max_tokens=4))
    removed = [e for e in eng.test_events if e.removed_hashes]
    assert removed, "LRU eviction under pressure must emit removed events"
    await eng.close()


def test_block_pool_lifecycle():
    removed = []
    pool = BlockPool(8, on_removed=lambda h: removed.extend(h or []))
    a = pool.allocate(3)
    assert a and len(a) == 3 and 0 not in a
    pool.register(a[0], seq_hash=111, tokens_hash=11, parent_hash=None)
    pool.register(a[1], seq_hash=222, tokens_hash=22, parent_hash=111)
    pool.release(a)
    # hashed blocks parked in LRU, unhashed freed
    assert pool.num_free_blocks == 7
    hit = pool.match_prefix([111, 222, 333])
    assert hit == [a[0], a[1]]
    pool.release(hit)
    # exhaust: allocate all 7 usable → evicts the two cached blocks
    got = pool.allocate(7)
    assert got is not None
    assert set(removed) == {111, 222}
    assert pool.allocate(1) is None


async def test_max_model_len_stops():
    eng = tiny_engine(max_model_len=32)
    toks, reason = await collect(eng, req(list(range(1, 30)), max_tokens=100))
    assert reason == FinishReason.LENGTH
    assert len(toks) <= 4
    await eng.close()


async def test_cancellation_unblocks_consumer():
    from dynamo_tpu.runtime.context import Context

    eng = tiny_engine()
    ctx = Context()
    got = []

    async def consume():
        async for out in eng.generate(req(list(range(1, 40)), max_tokens=100), ctx):
            got.append(out)

    task = asyncio.create_task(consume())
    await asyncio.sleep(0.3)
    ctx.cancel()
    await asyncio.wait_for(task, timeout=10)  # must not hang
    assert not eng.scheduler.running and not eng.scheduler.waiting
    await eng.close()


async def test_non_power_of_two_limits():
    eng = tiny_engine(max_num_seqs=3, max_num_batched_tokens=24,
                      prefill_buckets=(), decode_batch_buckets=())
    assert eng.args.decode_batch_buckets[-1] == 3
    assert eng.args.prefill_buckets[-1] == 24
    prompts = [list(range(b, b + 25)) for b in (1, 30, 60)]
    results = await asyncio.gather(*(collect(eng, req(p, max_tokens=4)) for p in prompts))
    assert all(len(t) == 4 for t, _ in results)
    await eng.close()


async def test_unchunked_oversized_prompt_fails_without_wedging():
    """Prompt > max_num_batched_tokens with chunking off must error, and a
    short prompt admitted alongside must still complete (no prefill wedge)."""
    eng = tiny_engine(enable_chunked_prefill=False)
    long_req = req(list(range(1, 100)))  # 99 tokens > 64 budget
    short_req = req(list(range(1, 10)), max_tokens=4)

    async def run(r):
        toks = []
        reason = None
        async for out in eng.generate(r):
            toks.extend(out.token_ids)
            if out.finish_reason is not None:
                reason = out.finish_reason
        return toks, reason

    (lt, lr), (st, sr) = await asyncio.gather(run(long_req), run(short_req))
    assert lr == FinishReason.ERROR
    assert sr == FinishReason.LENGTH and len(st) == 4
    await eng.close()


async def test_pallas_attention_engine_equivalence():
    """Engine outputs with the Pallas decode kernel (interpret on CPU) must
    match the XLA attention path token-for-token."""
    prompt = list(range(1, 40))
    # hd must be a lane multiple for the kernel: tiny() has hd=16 → the
    # kernel falls back; use a cfg with hd = 128
    cfg = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=128,
                      dtype="float32", max_position_embeddings=512)
    outs = []
    for use_pallas in (False, True):
        args = EngineArgs(block_size=8, num_blocks=64, max_num_seqs=4,
                          max_num_batched_tokens=64, max_model_len=128,
                          use_pallas_attention=use_pallas,
                          prefill_buckets=(8, 16, 32, 64),
                          decode_batch_buckets=(1, 2, 4))
        eng = AsyncJaxEngine(cfg, args)
        toks, reason = await collect(eng, req(prompt))
        outs.append(toks)
        await eng.close()
    assert outs[0] == outs[1]


@pytest.mark.slow
async def test_multi_step_decode_equivalence():
    """K-step fused decode must reproduce the single-step token stream,
    greedy and seeded-sampling alike, including finish mid-burst."""
    prompt = list(range(1, 20))
    for sampling in ({}, {"temperature": 0.8, "seed": 7},
                     {"temperature": 0.9, "top_k": 20, "seed": 3}):
        single = tiny_engine()
        want, wr = await collect(single, req(prompt, max_tokens=11, **sampling))
        await single.close()

        multi = tiny_engine(multi_step_decode=4)  # 11 % 4 != 0: mid-burst end
        got, gr = await collect(multi, req(prompt, max_tokens=11, **sampling))
        await multi.close()
        assert got == want and gr == wr


@pytest.mark.slow
async def test_multi_step_decode_concurrent_batch():
    eng = tiny_engine(multi_step_decode=4)
    prompts = [list(range(1, 10)), list(range(5, 40)), list(range(2, 17))]
    results = await asyncio.gather(
        *(collect(eng, req(p, max_tokens=6)) for p in prompts))
    await eng.close()
    solo = tiny_engine()
    for p, (got, _) in zip(prompts, results):
        want, _ = await collect(solo, req(p, max_tokens=6))
        assert got == want
    await solo.close()


@pytest.mark.slow
async def test_multi_step_decode_with_pallas_kernel():
    """Burst path + Pallas kernel (interpret on CPU) matches the XLA path."""
    prompt = list(range(1, 30))
    cfg = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=128,
                      dtype="float32", max_position_embeddings=512)
    outs = []
    for use_pallas in (False, True):
        args = EngineArgs(block_size=8, num_blocks=64, max_num_seqs=4,
                          max_num_batched_tokens=64, max_model_len=128,
                          use_pallas_attention=use_pallas,
                          multi_step_decode=3,
                          prefill_buckets=(8, 16, 32, 64),
                          decode_batch_buckets=(1, 2, 4))
        eng = AsyncJaxEngine(cfg, args)
        toks, _ = await collect(eng, req(prompt, max_tokens=9))
        outs.append(toks)
        await eng.close()
    assert outs[0] == outs[1] and len(outs[0]) == 9


async def test_engine_embed_normalized_and_padding_invariant():
    """embed(): L2-normalized vectors; padding must not change a row's
    embedding (mask correctness)."""
    eng = tiny_engine()
    a = list(range(1, 9))
    b = list(range(20, 45))
    v_joint = await eng.embed([a, b])  # padded batch (different lengths)
    v_solo = await eng.embed([a])
    assert abs(float(np.linalg.norm(v_joint[0])) - 1.0) < 1e-5
    np.testing.assert_allclose(np.asarray(v_joint[0]), np.asarray(v_solo[0]),
                               atol=1e-5, rtol=1e-5)
    # distinct inputs produce distinct embeddings
    assert abs(float(np.dot(v_joint[0], v_joint[1]))) < 0.999
    await eng.close()


async def test_embeddings_http_e2e():
    """/v1/embeddings through the full frontend + worker embed endpoint."""
    import aiohttp

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create()
    eng = tiny_engine()
    backend = rt.namespace("dynamo").component("backend")
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
    handle = await backend.endpoint("generate").serve_endpoint(
        DecodeWorkerHandler(eng).generate)
    eh = await backend.endpoint("embed").serve_endpoint(eng.embed_handler)
    card = ModelDeploymentCard(display_name="emb", kv_cache_block_size=4,
                               eos_token_ids=[2], tokenizer_ref="test")
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
        async with aiohttp.ClientSession() as http:
            resp = await http.post(
                f"http://127.0.0.1:{service.port}/v1/embeddings",
                json={"model": "emb",
                      "input": ["hello world", "the quick brown fox"]})
            assert resp.status == 200, await resp.text()
            body = await resp.json()
        assert body["object"] == "list" and len(body["data"]) == 2
        assert body["data"][0]["index"] == 0
        assert len(body["data"][0]["embedding"]) == eng.cfg.hidden_size
        assert body["usage"]["prompt_tokens"] > 0
    finally:
        await service.stop()
        await watcher.stop()
        await eh.stop(graceful=False)
        await handle.stop(graceful=False)
        await eng.close()
        await rt.shutdown()


async def test_preemption_never_evicts_planned_decode():
    """Memory pressure with mixed prefill+decode: planning a prefill chunk
    must never preempt a sequence already finalized into this step's decode
    batch (its freed block table would be indexed by the imminent jitted
    call — the bench-on-TPU IndexError). Under pressure everything still
    completes, possibly after recompute preemptions."""
    eng = tiny_engine(num_blocks=20, max_num_seqs=4,
                      max_num_batched_tokens=16, max_model_len=128,
                      prefill_buckets=(8, 16), decode_batch_buckets=(1, 2, 4))

    async def run(seed):
        prompt = [1 + (seed * 7 + i) % 200 for i in range(24)]
        toks, reason = await collect(eng, req(prompt, max_tokens=8))
        assert reason == FinishReason.LENGTH
        return toks

    results = await asyncio.gather(*(run(i) for i in range(4)))
    assert all(len(r) == 8 for r in results)
    await eng.close()


async def test_logit_bias_steers_and_bans():
    """OpenAI logit_bias: +100 forces a token, -100 bans it — applied in
    the engine sampler pre-sampling (the logits-processing surface)."""
    from dynamo_tpu.protocols import SamplingOptions

    eng = tiny_engine()
    prompt = list(range(1, 16))

    async def run(bias):
        r = PreprocessedRequest(
            model="tiny", token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0,
                                             logit_bias=bias))
        toks = []
        async for out in eng.generate(r):
            toks.extend(out.token_ids)
        return toks

    plain = await run(None)
    forced = await run({"37": 100.0})
    assert forced == [37, 37, 37, 37]
    banned = await run({str(plain[0]): -100.0})
    assert banned[0] != plain[0]
    # bias-free requests afterwards are unaffected
    assert await run(None) == plain
    await eng.close()


async def test_batched_prefill_plans_and_matches_sequential():
    """Concurrent same-size prompts share ONE packed launch (the ragged
    step co-schedules their chunks) and outputs equal sequential runs."""
    eng = tiny_engine(max_num_seqs=8, max_num_batched_tokens=64,
                      prefill_buckets=(16, 32, 64),
                      decode_batch_buckets=(1, 2, 4, 8))
    prompts = [[10 + i] + list(range(1, 14)) for i in range(4)]

    # sequential reference
    seq_out = [await collect(eng, req(p, max_tokens=4)) for p in prompts]

    # concurrent: watch the max co-scheduled chunk count per packed step
    max_chunks = 0
    orig = eng._run_ragged

    async def spy(plan):
        nonlocal max_chunks
        max_chunks = max(max_chunks, len(plan.prefill))
        return await orig(plan)

    eng._run_ragged = spy
    conc_out = await asyncio.gather(
        *(collect(eng, req(p, max_tokens=4)) for p in prompts))
    assert [t for t, _ in conc_out] == [t for t, _ in seq_out]
    assert max_chunks >= 2  # chunks co-scheduled into one packed step
    await eng.close()


async def test_prefill_runs_when_bucket_exceeds_budget():
    """Coarse custom prefill_buckets larger than max_num_batched_tokens
    must still serve (the padded-cost bound only gates ADDING batch rows)."""
    eng = tiny_engine(max_num_batched_tokens=50,
                      prefill_buckets=(16, 32, 64),
                      decode_batch_buckets=(1, 2))
    toks, reason = await asyncio.wait_for(
        collect(eng, req(list(range(1, 40)), max_tokens=3)), 60)
    assert len(toks) == 3 and reason == FinishReason.LENGTH
    await eng.close()


async def test_decode_batch_capped_at_largest_bucket():
    """More concurrent decode seqs than decode_batch_buckets[-1]: the
    scheduler must cap the decode (and spec/burst) batch at the largest
    bucket — the engine pads B with bucket_batch, so extra rows would
    index out of bounds in the step's batch arrays."""
    eng = tiny_engine(max_num_seqs=8, decode_batch_buckets=(1, 2))
    prompts = [list(range(1 + 7 * i, 7 * i + 6)) for i in range(5)]
    results = await asyncio.wait_for(
        asyncio.gather(*(collect(eng, req(p, max_tokens=4))
                         for p in prompts)), 120)
    for toks, reason in results:
        assert len(toks) == 4 and reason == FinishReason.LENGTH
    await eng.close()


@pytest.mark.parametrize("arch", [
    pytest.param("mla_tiny", marks=pytest.mark.slow), "gptoss_tiny", "moe_tiny"])
async def test_engine_embed_all_families(arch):
    """/v1/embeddings backing path must work for EVERY served family —
    MLA, gpt-oss (windows+sinks), MoE — via the serving forward (r2
    verdict #8: the dense-only embedding_forward refused these)."""
    from dynamo_tpu import models

    cfg = models.get_model_config(arch)
    args = EngineArgs(block_size=4, num_blocks=128, max_num_seqs=4,
                      max_num_batched_tokens=64, max_model_len=128)
    eng = AsyncJaxEngine(cfg, args)
    try:
        a = list(range(1, 9))
        b = list(range(20, 45))
        v_joint = await eng.embed([a, b])
        v_solo = await eng.embed([a])
        assert abs(float(np.linalg.norm(v_joint[0])) - 1.0) < 1e-4
        # padding/batch invariance: same input, same vector
        np.testing.assert_allclose(np.asarray(v_joint[0]),
                                   np.asarray(v_solo[0]),
                                   atol=2e-4, rtol=2e-4)
        assert abs(float(np.dot(np.asarray(v_joint[0]),
                                np.asarray(v_joint[1])))) < 0.999
    finally:
        await eng.close()


@pytest.mark.slow
async def test_engine_pp_serving_matches_single_device():
    """Full engine serving through the pipeline-parallel step (pp=2):
    greedy tokens must equal the single-device engine's exactly."""
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    async def run(mesh, **kw):
        cfg = ModelConfig.tiny()
        args = EngineArgs(block_size=4, num_blocks=128, max_num_seqs=8,
                          max_num_batched_tokens=64, max_model_len=256,
                          prefill_buckets=(8, 16, 32, 64),
                          decode_batch_buckets=(1, 2, 4, 8), **kw)
        eng = AsyncJaxEngine(cfg, args, mesh=mesh)
        outs = []
        for p in [list(range(1, 23)), list(range(5, 40))]:
            toks = []
            async for out in eng.generate(req(p)):
                toks.extend(out.token_ids)
            outs.append(toks)
        await eng.close()
        return outs

    want = await run(None)
    got = await run(make_mesh(MeshConfig(pp=2, dp=2, tp=2)))
    assert got == want


async def test_engine_pp_rejects_incompatible_config():
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = ModelConfig.tiny()
    mesh = make_mesh(MeshConfig(pp=8))  # 2 layers % 8 != 0
    with pytest.raises(ValueError, match="pp"):
        AsyncJaxEngine(cfg, EngineArgs(block_size=4, num_blocks=64),
                       mesh=mesh)
