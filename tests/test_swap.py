"""Preempt-to-swap: scheduler-driven KV swap-out/swap-in (ISSUE 4).

Under KV pressure the scheduler stages a victim's device pages in host
DRAM (same value/packed-quant bundle formats the G2 tier carries) and
swaps them back before the sequence's next step, instead of releasing the
blocks and re-prefilling from scratch. The hard guarantees covered here:

- a swapped-out→swapped-in sequence's token stream is BIT-IDENTICAL to a
  never-swapped run (greedy and seeded sampling, plain and int8 caches);
- with sufficient host budget the oversubscribed workload recomputes ZERO
  prefill tokens (the counters prove preemptions went through swap);
- budget exhaustion falls back to recompute preemption and still completes;
- cancelling a swapped sequence tears the host bundle + reservation down;
- per-request KV-event publish batching is the default (one chained stored
  event per prompt), with the DYN_KV_EVENT_PER_CHUNK escape hatch;
- on the long-prompt oversubscribed scenario the swap counters move and
  swap recomputes strictly fewer prefill tokens than forced recompute.
"""

import asyncio

import pytest

from dynamo_tpu.engine.cache import SwapStore
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio

BS = 4
N, ISL, OSL = 4, 32, 24


def pressure_engine(swap=True, pool="small", **kw) -> AsyncJaxEngine:
    """Engine whose pool holds ~half the workload's peak working set
    ("small") or all of it with headroom ("big" — never preempts)."""
    working = N * ((ISL + OSL + BS - 1) // BS)
    nb = {"small": working // 2 + 1, "big": working + 8}[pool]
    defaults = dict(block_size=BS, num_blocks=nb, max_num_seqs=N,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(ISL,), decode_batch_buckets=(N,),
                    enable_prefix_caching=False, preempt_swap=swap)
    defaults.update(kw)
    return AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(**defaults))


def prompt(i):
    return [(7 * i + j) % 200 + 1 for j in range(ISL)]


def req(tokens, max_tokens=OSL, **sampling) -> PreprocessedRequest:
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(**sampling))


async def collect(eng, r, ctx=None):
    toks, reason = [], None
    async for out in eng.generate(r, ctx):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            reason = out.finish_reason
    return toks, reason


async def run_workload(eng, **sampling):
    res = await asyncio.gather(
        *[collect(eng, req(prompt(i), **sampling)) for i in range(N)])
    return [t for t, _ in res]


# ------------------------------------------------------------- determinism


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("sampling", [dict(temperature=0.0),
                                      dict(temperature=0.9, seed=3)])
async def test_swap_roundtrip_bit_identical(kv_dtype, sampling):
    """A sequence that was swapped out and back resumes with EXACTLY the
    stream a never-swapped run produces — for plain and int8 caches, greedy
    and seeded sampling (the packed (q, s) bundle format makes the int8
    round-trip bit-exact by construction)."""
    e_swap = pressure_engine(pool="small", kv_cache_dtype=kv_dtype)
    e_big = pressure_engine(pool="big", kv_cache_dtype=kv_dtype)
    swapped = await run_workload(e_swap, **sampling)
    baseline = await run_workload(e_big, **sampling)
    assert e_swap.scheduler.preempt_swap_total > 0, \
        "scenario generated no swap preemptions — nothing was proven"
    assert e_big.scheduler.preempt_swap_total == 0
    assert swapped == baseline
    assert all(len(t) == OSL for t in swapped)
    await e_swap.close()
    await e_big.close()


async def test_oversubscribed_workload_recomputes_nothing():
    """With the host budget sufficient, preemption under the oversubscribed
    workload goes ENTIRELY through swap: zero recompute preemptions, zero
    recomputed prefill tokens, and the swap volume balances out."""
    eng = pressure_engine(pool="small")
    toks = await run_workload(eng)
    st = eng.swap_stats()
    assert all(len(t) == OSL for t in toks)
    assert st["preempt_swap"] > 0
    assert st["preempt_recompute"] == 0
    assert st["recomputed_tokens"] == 0
    assert st["swap_out_blocks"] > 0
    assert st["swap_out_blocks"] == st["swap_in_blocks"]
    # steady state: nothing left parked, budget fully returned
    assert st["swapped_seqs"] == 0
    assert st["swapped_blocks"] == 0
    assert st["swap_host_bytes"] == 0
    await eng.close()


async def test_budget_exhausted_falls_back_to_recompute():
    """swap_host_bytes too small for even one block: every preemption takes
    the classic release-and-recompute path, and the workload still
    completes with identical tokens."""
    eng = pressure_engine(pool="small", swap_host_bytes=64)
    base = pressure_engine(pool="big")
    toks = await run_workload(eng)
    baseline = await run_workload(base)
    st = eng.swap_stats()
    assert st["swap_out_blocks"] == 0
    assert st["preempt_swap"] == 0
    assert st["preempt_recompute"] > 0
    assert toks == baseline  # recompute is exact too, just wasteful
    await eng.close()
    await base.close()


async def test_cancel_while_swapped_tears_down():
    """Cancelling a sequence parked in the swapped queue frees its host
    bundle + budget reservation; the remaining streams finish normally."""

    class Ctx:
        cancelled = False
        id = "cancel-target"

    eng = pressure_engine(pool="small")
    ctxs = [Ctx() for _ in range(N)]
    tasks = [asyncio.ensure_future(collect(eng, req(prompt(i)), ctxs[i]))
             for i in range(N)]
    # wait for a victim to land in the swapped queue, then cancel it
    for _ in range(20000):
        if eng.scheduler.swapped:
            break
        await asyncio.sleep(0.001)
    assert eng.scheduler.swapped, "no sequence was ever swapped out"
    victim = eng.scheduler.swapped[0]
    victim.ctx.cancelled = True
    eng._wake.set()
    results = await asyncio.gather(*tasks)
    by_id = {id(c): r for c, r in zip(ctxs, results)}
    # the cancelled stream ended early; every other stream is complete
    assert len(by_id[id(victim.ctx)][0]) < OSL
    done = [r for c, r in zip(ctxs, results) if c is not victim.ctx]
    assert all(len(t) == OSL for t, _ in done)
    assert not eng.scheduler.swapped
    # close() drains in-flight copy tasks; teardown must have returned
    # every reserved host byte by then
    await eng.close()
    assert eng._swap.used == 0
    assert eng.pool.swapped_blocks == 0


# ------------------------------------------------------- budget accounting


def test_swap_store_budget_shared_with_g2():
    """The SwapStore budget is shared with the G2 tier — in BOTH
    directions: G2 residency shrinks what swap may reserve, and swap
    reservations shrink what the G2 tier may hold (its puts evict/drop
    down to capacity − swap bytes), so combined host DRAM stays inside
    the one configured allowance."""
    import numpy as np

    from dynamo_tpu.kvbm.tiers import HostTier

    g2_used = {"v": 0}
    store = SwapStore(1000, external_used=lambda: g2_used["v"])
    assert store.reserve(600)
    assert not store.reserve(600)  # over budget
    store.release(600)
    g2_used["v"] = 700
    assert not store.reserve(600)  # G2 residency counts against swap
    assert store.reserve(300)
    store.release(300)
    assert store.used == 0

    # the reverse direction: HostTier puts respect swap reservations
    # (host and store2 reference each other — the shared-allowance pair
    # the engine wires when swap_host_bytes is None and G2 is configured)
    host = HostTier(1000, external_used=lambda: store2.used)
    store2 = SwapStore(1000, external_used=lambda: host.used)
    blk = np.zeros(150, np.uint8)  # 300 bytes per (k, v) entry
    assert host.put(1, blk, blk) == [] and 1 in host
    assert host.put(2, blk, blk) == [] and 2 in host
    assert not store2.reserve(500)  # only 400 left; no make_room wired
    assert store2.reserve(300)
    evicted = host.put(3, blk, blk)  # 600 + 300 + 300 > 1000 → evict LRU
    assert 3 in host and [e[0] for e in evicted] == [1]
    assert host.used + store2.used <= 1000
    store2.release(300)


def test_swap_reserve_evicts_full_g2_lru():
    """A G2 LRU that has naturally filled the shared allowance must YIELD
    to a swap reservation (KvbmManager.make_host_room): its entries are
    redundant cache copies, while the victim's KV would otherwise be
    discarded and re-prefilled. Without this, steady-state offload
    traffic permanently disables swap in the flagship KVBM config."""
    import numpy as np

    from dynamo_tpu.kvbm.manager import KvbmManager

    blk = np.zeros(150, np.uint8)  # 300 bytes per (k, v) entry
    mgr = KvbmManager(host_bytes=1200)
    store = SwapStore(1200, external_used=lambda: mgr.host.used,
                      make_room=mgr.make_host_room)
    for h in (1, 2, 3, 4):
        mgr.put(h, blk, blk)
    assert mgr.host.used == 1200  # LRU at capacity: allowance exhausted
    assert store.reserve(700)     # evicts G2 LRU entries to fit
    assert mgr.host.used + store.used <= 1200
    assert 4 in mgr.host          # newest entries survive (LRU eviction)
    store.release(700)


# ------------------------------------------------- per-request KV batching


async def _prefill_events(per_chunk: bool):
    events = []
    eng = AsyncJaxEngine(
        ModelConfig.tiny(),
        EngineArgs(block_size=BS, num_blocks=128, max_num_seqs=2,
                   max_num_batched_tokens=16, max_model_len=256,
                   prefill_buckets=(16,), decode_batch_buckets=(1, 2),
                   kv_event_per_chunk=per_chunk),
        event_cb=events.append)
    toks, _ = await collect(eng, req(list(range(1, 49)), max_tokens=2))
    assert len(toks) == 2
    await eng.close()
    # stored events covering the 12 PROMPT blocks (48 tokens / bs 4);
    # decode-block events (if any) come after and are not counted
    stored = [e for e in events if e.stored_blocks]
    n_prompt_blocks = 48 // BS
    covered, prompt_events = 0, []
    for e in stored:
        prompt_events.append(len(e.stored_blocks))
        covered += len(e.stored_blocks)
        if covered >= n_prompt_blocks:
            break
    return prompt_events, n_prompt_blocks


async def test_kv_events_batch_per_request_by_default():
    """A 3-chunk prefill publishes ONE chained stored event for the whole
    prompt (fleet_bench: per-chunk publishing is 11% under the 70B
    requirement; per-request has 2.3x headroom)."""
    events, n_blocks = await _prefill_events(per_chunk=False)
    assert events == [n_blocks]


async def test_kv_events_flush_when_last_chunk_fills_no_block():
    """Regression: a prompt whose FINAL chunk registers no new full block
    (partial tail, e.g. 34 tokens with bs=4 and 16-token chunks: commits
    at 16/32/34, the last adding no full block) must still flush the
    batched chain AT prompt completion — not defer it until the first
    decode-filled block or finish."""
    events = []
    eng = AsyncJaxEngine(
        ModelConfig.tiny(),
        EngineArgs(block_size=BS, num_blocks=128, max_num_seqs=2,
                   max_num_batched_tokens=16, max_model_len=256,
                   prefill_buckets=(16,), decode_batch_buckets=(1, 2)),
        event_cb=events.append)
    sink = asyncio.Queue()
    r = req(list(range(1, 35)), max_tokens=1)
    seq = await eng._new_seq(r, None, sink)
    eng.scheduler.add(seq)
    eng._wake.set()
    eng._ensure_loop()
    out = await sink.get()  # first token => prompt fully committed
    assert out is not None and out.token_ids
    stored = [e for e in events if e.stored_blocks]
    # 34 tokens = 8 full blocks, published as ONE chain at completion
    assert [len(e.stored_blocks) for e in stored] == [34 // BS]
    await eng.close()


async def test_kv_events_per_chunk_escape_hatch():
    """kv_event_per_chunk=True (the DYN_KV_EVENT_PER_CHUNK escape hatch)
    restores one stored event per prefill chunk."""
    events, n_blocks = await _prefill_events(per_chunk=True)
    assert len(events) >= 3  # one per 16-token chunk
    assert sum(events) == n_blocks


# ------------------------------------------------ long-prompt mem pressure


async def test_mem_pressure_bench_smoke():
    """Oversubscribed KV with long prompts (pool ~45% of the working set),
    the same seeded workload with preempt-to-swap and with forced recompute
    preemption: no token is lost in either arm, the swap counters move and
    balance, and swap recomputes strictly fewer prefill tokens (the waste
    of the recompute path is re-PREFILL work, so it scales with ISL)."""
    import numpy as np

    cfg = ModelConfig.tiny()
    n, isl, osl = 6, 192, 48
    working_blocks = n * ((isl + osl + BS - 1) // BS)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, isl).tolist()
               for _ in range(n)]

    async def arm(swap: bool) -> dict:
        eng = AsyncJaxEngine(cfg, EngineArgs(
            block_size=BS, num_blocks=int(working_blocks * 0.45) + 1,
            max_num_seqs=n, max_num_batched_tokens=isl,
            max_model_len=2 * (isl + osl), prefill_buckets=(isl,),
            decode_batch_buckets=(n,), enable_prefix_caching=False,
            preempt_swap=swap))
        try:
            res = await asyncio.gather(
                *[collect(eng, req(p, osl, temperature=0.0))
                  for p in prompts])
            assert [len(t) for t, _ in res] == [osl] * n
            return eng.swap_stats()
        finally:
            await eng.close()

    s = await arm(True)
    r = await arm(False)
    assert s["swap_out_blocks"] > 0
    assert s["swap_in_blocks"] == s["swap_out_blocks"]
    assert s["preempt_swap"] > 0
    assert r["preempt_recompute"] > 0
    assert s["recomputed_tokens"] < r["recomputed_tokens"]
