"""Simulated engine: continuous batching, chunked prefill, prefix cache,
genuine KV events and load metrics — no accelerator needed.

Rebuild of the reference's mocker (ref: lib/llm/src/mocker/{engine.rs:48,
scheduler.rs:240,kv_manager.rs,evictor.rs,protocols.rs:67-100}): the mocker is
the backbone of router/planner/frontend tests because it emits *real* KV
events (same hash domain as the frontend) and real ForwardPassMetrics while
modeling engine timing (prefill cost, chunked prefill, decode batching,
watermark-based admission, LRU prefix-cache eviction).

The token stream it produces is deterministic per request (seeded by the
prompt) so tests can assert determinism.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field
from typing import AsyncIterator, Optional

from dynamo_tpu.engine.scheduler import share_prefill_budget
from dynamo_tpu.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.router.protocols import ForwardPassMetrics, KvStats, StoredBlock, WorkerStats
from dynamo_tpu.router.publisher import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.runtime.chaos import get_chaos
from dynamo_tpu.runtime.context import Context, StreamError
from dynamo_tpu.tokens import TokenBlockSequence

logger = logging.getLogger("dynamo.mocker")


@dataclass
class MockEngineArgs:
    """ref: mocker/protocols.rs:67-100 (same knobs, same defaults where sane)."""

    num_gpu_blocks: int = 8192
    block_size: int = 16
    max_num_seqs: int = 256
    max_num_batched_tokens: int = 8192
    enable_prefix_caching: bool = True
    enable_chunked_prefill: bool = True
    watermark: float = 0.01
    speedup_ratio: float = 1.0
    #: base + per-token prefill cost (ms), divided by speedup_ratio
    prefill_base_ms: float = 5.0
    prefill_per_token_ms: float = 0.02
    #: base + per-seq decode cost (ms) per iteration
    decode_base_ms: float = 2.0
    decode_per_seq_ms: float = 0.05
    vocab_size: int = 1000
    #: data-parallel ranks simulated by ONE mocker process (ref:
    #: mocker/protocols.rs:95 + engine.rs:115-127 — one scheduler, KV-event
    #: stream and metrics publisher per rank). Ranks surface as separate
    #: instances on the endpoint, so the router sees per-rank event
    #: interleaving exactly as it would from a real DP fleet.
    dp_size: int = 1
    #: simulated engine-initialization delay before serving (ref:
    #: protocols.rs:98 startup_time)
    startup_time: Optional[float] = None
    #: token-budget planning (the real engine's ragged-step mode,
    #: docs/performance.md): decode rows and prefill chunks co-schedule
    #: under ONE max_num_batched_tokens budget per step — decode rows
    #: reserve a token each first, prefill fills the remainder — and a
    #: mixed step costs a SINGLE launch (one base latency, not
    #: prefill_base + decode_base). Fleet-level tests (autoscale, QoS,
    #: chaos) therefore exercise the new planning mode without a real
    #: model; False restores the independent prefill/decode budgets.
    token_budget_plan: bool = True


#: the mocker's constraint alphabet (structured-decoding parity): token id
#: i decodes to one printable char, id 0 reserved — the same shape the
#: engine-level guided tests use, so fleet tests can assert schema-valid
#: canned output by decoding the token stream against it
_GUIDED_VOCAB: list = []


def mock_guided_vocab() -> list[str]:
    global _GUIDED_VOCAB
    if not _GUIDED_VOCAB:
        _GUIDED_VOCAB = [""] + [chr(32 + i) for i in range(95)]
    return _GUIDED_VOCAB


@dataclass
class _Seq:
    request_id: str
    req: PreprocessedRequest
    ctx: Context
    out_queue: "asyncio.Queue[Optional[LLMEngineOutput]]"
    blocks: TokenBlockSequence = None  # full sequence incl. generated
    prefill_pos: int = 0  # tokens prefilled so far
    cached_tokens: int = 0  # tokens skipped via prefix cache
    generated: int = 0
    rng: random.Random = None
    owned_block_hashes: list[int] = field(default_factory=list)
    finished: Optional[str] = None
    #: guided-decoding cursor over mock_guided_vocab (llm/guided
    #: GuidedState via structured.build_guided_state) — None = free decode
    guided: object = None

    @property
    def isl(self) -> int:
        return len(self.req.token_ids)

    @property
    def in_prefill(self) -> bool:
        return self.prefill_pos < self.isl

    @property
    def to_prefill(self) -> int:
        return self.isl - self.prefill_pos


class KvCacheSim:
    """Block pool with active refcounts + inactive LRU prefix cache.

    Mirrors the reference's KvManager+evictor semantics (ref: mocker/
    kv_manager.rs, evictor.rs): blocks are keyed by chained sequence hash;
    completed requests' blocks drop into an LRU reuse pool; admission needs
    free = capacity - active - watermark; storing evicts LRU inactive blocks.
    """

    def __init__(self, capacity: int, watermark: float):
        self.capacity = capacity
        self.watermark_blocks = int(capacity * watermark)
        self.active: dict[int, int] = {}  # seq_hash -> refcount
        self.inactive: dict[int, float] = {}  # seq_hash -> last_use (LRU)
        #: optional WorkerKvLedger (observability/kvaudit.py) — real-
        #: engine parity: membership mirrors active ∪ inactive, so the
        #: KV audit plane measures mocker fleets too
        self.ledger = None

    @property
    def used_blocks(self) -> int:
        return len(self.active) + len(self.inactive)

    @property
    def free_blocks(self) -> int:
        return self.capacity - self.used_blocks

    def can_allocate(self, n: int) -> bool:
        return self.free_blocks + len(self.inactive) - self.watermark_blocks >= n

    def lookup_prefix(self, seq_hashes: list[int]) -> int:
        """Longest cached prefix (active or inactive), in blocks."""
        n = 0
        for h in seq_hashes:
            if h in self.active or h in self.inactive:
                n += 1
            else:
                break
        return n

    def acquire(self, seq_hash: int) -> tuple[bool, list[int]]:
        """Activate a block; returns (is_new_block, evicted_hashes)."""
        evicted: list[int] = []
        if seq_hash in self.active:
            self.active[seq_hash] += 1
            return False, evicted
        if seq_hash in self.inactive:
            del self.inactive[seq_hash]
            self.active[seq_hash] = 1
            return False, evicted
        while self.free_blocks < 1 and self.inactive:
            lru = min(self.inactive, key=self.inactive.get)
            del self.inactive[lru]
            if self.ledger is not None:
                self.ledger.remove("g1", lru)
            evicted.append(lru)
        self.active[seq_hash] = 1
        if self.ledger is not None:
            self.ledger.add("g1", seq_hash)
        return True, evicted

    def release(self, seq_hash: int, cache: bool) -> Optional[int]:
        """Drop one reference; returns the hash if the block left the pool."""
        rc = self.active.get(seq_hash)
        if rc is None:
            return None
        if rc > 1:
            self.active[seq_hash] = rc - 1
            return None
        del self.active[seq_hash]
        if cache:
            self.inactive[seq_hash] = time.monotonic()
            return None
        if self.ledger is not None:
            self.ledger.remove("g1", seq_hash)
        return seq_hash


class MockEngine:
    """Async continuous-batching simulator serving PreprocessedRequests."""

    def __init__(
        self,
        args: MockEngineArgs,
        kv_publisher: Optional[KvEventPublisher] = None,
        metrics_publisher: Optional[WorkerMetricsPublisher] = None,
    ):
        self.args = args
        self.kv_publisher = kv_publisher
        self.metrics_publisher = metrics_publisher
        self.cache = KvCacheSim(args.num_gpu_blocks, args.watermark)
        #: KV audit plane parity (observability/kvaudit.py): the mocker
        #: keeps the same residency ledger a real engine does, served by
        #: run_mocker via the kv_digest wire op; wiring it into the
        #: publisher makes resync replays ledger-reconciling here too
        from dynamo_tpu.observability.kvaudit import WorkerKvLedger
        self.kv_ledger = WorkerKvLedger()
        self.cache.ledger = self.kv_ledger
        if (args.enable_prefix_caching and kv_publisher is not None
                and kv_publisher.ledger is None):
            # caching-off mockers announce blocks they release silently
            # (pre-existing advert semantics) — a ledger-reconciling
            # replay there would retract every advert, so the audit
            # plane only covers prefix-caching workers (engine parity)
            kv_publisher.ledger = self.kv_ledger
        self.waiting: list[_Seq] = []
        self.running: list[_Seq] = []
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._stopped = False
        self.iterations = 0
        #: step flight recorder parity with the real engine
        #: (observability/flight.py): every simulated step appends one
        #: tagged record, so fleet-level tests and `dynctl top` see the
        #: same timeline shape without an accelerator. run_mocker
        #: registers it per rank for the fan-out endpoint.
        from dynamo_tpu.observability.flight import FlightRecorder
        self.flight = FlightRecorder(service="mocker")
        self._last_empty_rec = 0.0
        #: chaos worker.kill (runtime/chaos.py): hard-died mid-step —
        #: in-flight queues never resolve, death reaches the fleet only
        #: via lease expiry (same contract as the real engine)
        self.killed = False
        self.on_kill: list = []

    async def start(self) -> "MockEngine":
        self._task = asyncio.get_running_loop().create_task(self._engine_loop())
        return self

    async def stop(self):
        self._stopped = True
        self._wake.set()
        if self._task:
            await self._task
        name = getattr(self, "_flight_name", None)
        if name is not None:  # set by run_mocker's per-rank registration
            from dynamo_tpu.observability.flight import unregister_recorder
            unregister_recorder(name)

    # -- public engine interface ------------------------------------------
    async def generate(self, req, ctx: Context) -> AsyncIterator[dict]:
        """Endpoint handler: yields LLMEngineOutput wire dicts."""
        if isinstance(req, dict):
            req = PreprocessedRequest.from_wire(req)
        if getattr(ctx, "expired", False):
            # an expired request must never enter the scheduler
            yield LLMEngineOutput(
                finish_reason=FinishReason.DEADLINE).to_wire()
            return
        seq = _Seq(
            request_id=ctx.id,
            req=req,
            ctx=ctx,
            out_queue=asyncio.Queue(),
            blocks=TokenBlockSequence.from_tokens(req.token_ids, self.args.block_size),
            rng=random.Random(req.sampling_options.seed if req.sampling_options.seed is not None
                              else hash(tuple(req.token_ids)) & 0xFFFFFFFF),
        )
        if req.sampling_options.guided:
            # structured-decoding parity: fleet tests (QoS/autoscale/chaos)
            # carry constrained traffic through the mocker too — compile
            # the constraint over the mock alphabet (cached + counted like
            # the real engine's admissions) and emit schema-valid output
            from dynamo_tpu.structured import build_guided_state
            seq.guided = await asyncio.to_thread(
                build_guided_state, req.sampling_options.guided,
                mock_guided_vocab(), req.eos_token_ids or [], None)
        self.waiting.append(seq)
        self._wake.set()
        # same engine-side phase spans the real engine records, so the
        # mock path yields a full stitched trace in accelerator-less tests
        # — including the flight identity + step-seq interval attributes
        # the attribution join keys on (observability/attribution.py)
        from dynamo_tpu.observability import get_tracer
        from dynamo_tpu.observability.flight import flight_instance

        tracer = get_tracer()
        t0 = time.time()
        seq0 = self.flight.seq_now
        seq_first = None
        t_first = None
        n_tokens = 0
        try:
            while True:
                out = await seq.out_queue.get()
                if out is None:
                    return
                if isinstance(out, Exception):
                    raise out  # chaos step failure → retryable stream error
                if t_first is None and out.token_ids:
                    t_first = time.time()
                    seq_first = self.flight.seq_now
                    tracer.record("engine.ttft", ctx, start=t0, end=t_first,
                                  service="engine",
                                  prompt_tokens=len(req.token_ids),
                                  cached_tokens=seq.cached_tokens,
                                  flight_instance=flight_instance(),
                                  flight_name=getattr(
                                      self, "_flight_name", "mocker"),
                                  seq0=seq0, seq1=seq_first)
                    out.flight = {"worker": flight_instance(),
                                  "recorder": getattr(
                                      self, "_flight_name", "mocker"),
                                  "seq": seq_first}
                n_tokens += len(out.token_ids)
                yield out.to_wire()
                if out.finish_reason is not None:
                    return
        finally:
            if t_first is not None:
                tracer.record("engine.decode", ctx, start=t_first,
                              end=time.time(), service="engine",
                              tokens=n_tokens,
                              flight_instance=flight_instance(),
                              flight_name=getattr(
                                  self, "_flight_name", "mocker"),
                              seq0=seq_first, seq1=self.flight.seq_now)

    # -- engine loop -------------------------------------------------------
    async def _engine_loop(self):
        try:
            while not self._stopped:
                if not self.running and not self.waiting:
                    self._wake.clear()
                    try:
                        await asyncio.wait_for(self._wake.wait(), timeout=0.5)
                    except asyncio.TimeoutError:
                        continue
                    continue
                await self._step()
        except asyncio.CancelledError:
            pass
        except Exception:
            logger.exception("mocker engine loop crashed")

    async def _step(self):
        self.iterations += 1
        chaos = get_chaos()
        if (chaos is not None and self.running
                and chaos.should_error("worker.kill")):
            # seeded hard death (SIGKILL-grade): stop the loop without
            # resolving any in-flight queue — no drain, no goodbye
            logger.warning("chaos: worker.kill fired — mocker hard-dying "
                           "with %d running seqs", len(self.running))
            self.killed = True
            self._stopped = True
            for cb in list(self.on_kill):
                try:
                    cb()
                except Exception:
                    logger.exception("on_kill hook failed")
            return
        if (chaos is not None and self.running
                and chaos.should_error("engine.step")):
            # injected step crash: in-flight streams fail RETRYABLY so the
            # frontend's Migration operator re-issues them elsewhere — same
            # contract as the real engine's chaos hook
            for seq in self.running:
                if seq.finished is None:
                    seq.finished = FinishReason.ERROR
                    seq.out_queue.put_nowait(StreamError(
                        "chaos: injected engine step error"))
            self._reap_finished()
            return
        self._admit()
        # plan-time deadline enforcement: an expired sequence spends no
        # further simulated step and finishes with the "deadline" reason.
        # The WAITING queue is swept too (same contract as the real
        # scheduler): a request starved behind a saturated batch must not
        # hang past its budget waiting for an admission slot.
        for seq in self.running:
            if seq.finished is None and getattr(seq.ctx, "expired", False):
                seq.finished = FinishReason.DEADLINE
                seq.out_queue.put_nowait(LLMEngineOutput(
                    finish_reason=FinishReason.DEADLINE))
        for seq in list(self.waiting):
            if getattr(seq.ctx, "expired", False):
                self.waiting.remove(seq)
                seq.out_queue.put_nowait(LLMEngineOutput(
                    finish_reason=FinishReason.DEADLINE))
                seq.out_queue.put_nowait(None)
        # orphan-cancellation sweep (front-door kill hygiene, docs/
        # robustness.md): a cancelled context must free its slot whether
        # the row is decoding, MID-PREFILL, or still WAITING. Response-
        # plane peer death cancels a dead frontend's seqs; without this
        # sweep a prefilling/queued orphan would keep burning budget and
        # holding blocks until it finished naturally, so the BlockPool
        # would not return to its pre-request count.
        for seq in self.running:
            if seq.finished is None and seq.ctx.cancelled:
                seq.finished = FinishReason.CANCELLED
                seq.out_queue.put_nowait(LLMEngineOutput.cancelled())
        for seq in list(self.waiting):
            if seq.ctx.cancelled:
                self.waiting.remove(seq)
                seq.out_queue.put_nowait(LLMEngineOutput.cancelled())
                seq.out_queue.put_nowait(None)
        if self.args.token_budget_plan:
            # ragged-style step: decode rows spend the shared budget first
            # (one token each), prefill chunks fill what remains, and the
            # whole step is ONE launch — one base cost covers both kinds
            budget = self.args.max_num_batched_tokens
            decoded = await self._run_decode(
                max_rows=min(budget, self.args.max_num_seqs))
            prefill_tokens = await self._run_prefill_chunk(
                budget=budget - decoded)
            ms = 0.0
            if prefill_tokens or decoded:
                ms = (max(self.args.prefill_base_ms if prefill_tokens else 0.0,
                          self.args.decode_base_ms if decoded else 0.0)
                      + prefill_tokens * self.args.prefill_per_token_ms
                      + decoded * self.args.decode_per_seq_ms)
        else:
            prefill_tokens = await self._run_prefill_chunk()
            decoded = await self._run_decode()
            # simulated iteration latency: two independent launches
            ms = 0.0
            if prefill_tokens:
                ms += self.args.prefill_base_ms + prefill_tokens * self.args.prefill_per_token_ms
            if decoded:
                ms += self.args.decode_base_ms + decoded * self.args.decode_per_seq_ms
        if ms:
            await asyncio.sleep(ms / 1000.0 / self.args.speedup_ratio)
        else:
            await asyncio.sleep(0)
        self._flight_record(prefill_tokens, decoded, ms)
        self._reap_finished()
        await self._publish_metrics()

    def _flight_record(self, prefill_tokens: int, decoded: int,
                       ms: float) -> None:
        """Real-engine flight parity: one record per simulated step. An
        admission-blocked spin (work queued, nothing runnable — the memory
        bubble) records ``empty`` at most every 10 ms so the busy-wait
        cannot flood the ring with identical bubbles."""
        if not self.flight.enabled:
            return
        if not prefill_tokens and not decoded:
            if not (self.waiting or self.running):
                return
            now = time.monotonic()
            if now - self._last_empty_rec < 0.01:
                return
            self._last_empty_rec = now
            self.flight.record(
                "empty", 0.0, waiting=len(self.waiting),
                running=len(self.running),
                kv_tiers={"g1": self.cache.used_blocks})
            return
        chunks = sum(1 for s in self.running if s.in_prefill)
        self.flight.record(
            "mock", ms / self.args.speedup_ratio,
            decode_rows=decoded, prefill_chunks=chunks,
            chunk_tokens=prefill_tokens,
            waiting=len(self.waiting), running=len(self.running),
            # per-row constraint shape parity with the real engine's
            # records (docs/structured.md): fleet views show constrained
            # traffic on mocker fleets too
            constrained_rows=sum(1 for s in self.running
                                 if s.guided is not None
                                 and not s.in_prefill and not s.finished),
            kv_tiers={"g1": self.cache.used_blocks},
            # step↔request linkage parity (attribution join): the mocker's
            # request_id IS the Context id
            decode_ids=[s.request_id for s in self.running
                        if not s.in_prefill and s.finished is None],
            prefill_ids=[s.request_id for s in self.running
                         if s.in_prefill])

    def _admit(self):
        while self.waiting and len(self.running) < self.args.max_num_seqs:
            seq = self.waiting[0]
            needed = len(seq.blocks.blocks) + 1
            if not self.cache.can_allocate(needed):
                break
            self.waiting.pop(0)
            if self.args.enable_prefix_caching:
                cached = self.cache.lookup_prefix(seq.blocks.sequence_hashes())
                seq.cached_tokens = cached * self.args.block_size
                seq.prefill_pos = min(seq.cached_tokens, seq.isl)
            self.running.append(seq)

    async def _run_prefill_chunk(self, budget: Optional[int] = None) -> int:
        """One step's prefill. With chunked prefill the budget is shared as
        the real scheduler shares it (``share_prefill_budget``: fewest
        remaining tokens first, half kept for the oldest prompt; no row cap
        here); without it whole prompts run in admission order."""
        if budget is None:
            budget = self.args.max_num_batched_tokens
        seqs = [s for s in self.running if s.in_prefill and not s.finished]
        if self.args.enable_chunked_prefill and seqs:
            others = sorted(seqs[1:], key=lambda s: s.to_prefill)
            first, chunks = share_prefill_budget(
                seqs[0].to_prefill, [s.to_prefill for s in others], budget,
                len(seqs))
            shares = [(seqs[0], first), *zip(others, chunks)]
        else:
            shares = [(s, s.to_prefill) for s in seqs]
        total = 0
        for seq, chunk in shares:
            if budget <= 0:
                break
            start_block = seq.prefill_pos // self.args.block_size
            seq.prefill_pos += chunk
            budget -= chunk
            total += chunk
            end_block = seq.prefill_pos // self.args.block_size
            await self._store_blocks(seq, start_block, end_block)
        return total

    async def _store_blocks(self, seq: _Seq, start_block: int, end_block: int):
        """Acquire+announce newly-filled complete blocks [start, end)."""
        blocks = seq.blocks.blocks[start_block:end_block]
        if not blocks:
            return
        stored: list[StoredBlock] = []
        evicted_all: list[int] = []
        parent = seq.blocks.blocks[start_block - 1].sequence_hash if start_block > 0 else None
        for b in blocks:
            is_new, evicted = self.cache.acquire(b.sequence_hash)
            seq.owned_block_hashes.append(b.sequence_hash)
            evicted_all.extend(evicted)
            if is_new:
                stored.append(StoredBlock(block_hash=b.sequence_hash, tokens_hash=b.block_hash))
        if self.kv_publisher:
            if evicted_all:
                await self.kv_publisher.publish_removed(evicted_all)
            if stored:
                await self.kv_publisher.publish_stored(parent, stored)

    async def _run_decode(self, max_rows: Optional[int] = None) -> int:
        n = 0
        for seq in self.running:
            if seq.in_prefill or seq.finished:
                continue
            if max_rows is not None and n >= max_rows:
                break  # token budget spent: the row waits one step
            if seq.ctx.cancelled:
                seq.finished = FinishReason.CANCELLED
                seq.out_queue.put_nowait(LLMEngineOutput.cancelled())
                continue
            n += 1
            max_tokens = seq.req.stop_conditions.max_tokens or 64
            min_tokens = seq.req.stop_conditions.min_tokens or 0
            eos = False
            guided_stop = False
            if seq.guided is not None:
                # constrained row: deterministic greedy walk of the mask —
                # lowest allowed id each step, so the emitted stream is
                # schema-valid by construction (EOS joins the set only
                # where the constraint can terminate)
                gs = seq.guided
                hi = min(len(mock_guided_vocab()), self.args.vocab_size)
                ids = gs.allowed_token_ids(hi)
                if min_tokens > seq.generated:
                    non_eos = [t for t in ids if t not in gs.eos_ids]
                    ids = non_eos or ids
                if not ids:
                    # stranded (possible only past the liveness cap):
                    # finish like the real scheduler would
                    seq.finished = FinishReason.STOP
                    seq.out_queue.put_nowait(LLMEngineOutput(
                        finish_reason=FinishReason.STOP))
                    continue
                tok = ids[0]
                gs.advance(tok)
                eos = (tok in gs.eos_ids
                       and not seq.req.stop_conditions.ignore_eos)
                guided_stop = (gs.exhausted
                               or (gs.done and seq.generated >= min_tokens))
            else:
                tok = seq.rng.randint(10, self.args.vocab_size - 1)
                if seq.req.eos_token_ids and seq.generated >= min_tokens and not seq.req.stop_conditions.ignore_eos:
                    # small chance of sampling EOS to model natural stops
                    if seq.rng.random() < 0.02:
                        tok = seq.req.eos_token_ids[0]
                        eos = True
            new_block = seq.blocks.push_token(tok)
            if new_block is not None:
                await self._store_blocks(
                    seq, len(seq.blocks.blocks) - 1, len(seq.blocks.blocks)
                )
            seq.generated += 1
            finish = None
            if eos:
                finish = FinishReason.EOS
            elif guided_stop and seq.generated >= min_tokens:
                # constraint completed/exhausted: stop instead of free-
                # running past it (scheduler.check_finish parity)
                finish = FinishReason.STOP
            elif seq.generated >= max_tokens:
                finish = FinishReason.LENGTH
            seq.finished = finish
            seq.out_queue.put_nowait(LLMEngineOutput(token_ids=[tok], finish_reason=finish))
        return n

    def _reap_finished(self):
        still = []
        for seq in self.running:
            if seq.finished is None:
                still.append(seq)
                continue
            cache = self.args.enable_prefix_caching
            for h in seq.owned_block_hashes:
                gone = self.cache.release(h, cache)
                # release without caching: block disappears silently; events
                # for disappeared blocks are published on next eviction sweep
            seq.out_queue.put_nowait(None)
        self.running = still

    async def _publish_metrics(self):
        if not self.metrics_publisher or self.iterations % 8:
            return
        m = ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_active_slots=len(self.running),
                request_total_slots=self.args.max_num_seqs,
                num_requests_waiting=len(self.waiting),
            ),
            kv_stats=KvStats(
                kv_active_blocks=len(self.cache.active),
                kv_total_blocks=self.cache.capacity,
                gpu_cache_usage_perc=self.cache.used_blocks / self.cache.capacity,
            ),
        )
        try:
            await self.metrics_publisher.publish(m)
        except Exception:
            logger.exception("metrics publish failed")
