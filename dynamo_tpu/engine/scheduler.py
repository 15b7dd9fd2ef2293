"""Continuous batching scheduler: admission, chunked prefill, decode batching.

Pure host-side logic (no jax imports) mirroring the semantics of the
reference's engine schedulers it delegates to, and of its own mocker
scheduler (ref: lib/llm/src/mocker/scheduler.rs:240 — admission watermark,
chunked prefill budget, preemption; vLLM-style recompute preemption):

- A sequence's lifecycle: waiting → running (prefill chunks → decode steps)
  → finished, with a ``swapped`` station between waiting and running:
  preempted victims whose KV was staged to host DRAM (preempt-to-swap) park
  there and re-enter ``running`` at their old progress once blocks free up —
  only when the host budget is exhausted (or a bundle is torn down) does a
  victim fall back to the classic release-and-recompute path.
- ``num_computed`` counts tokens whose KV is in the paged cache;
  ``remaining = len(tokens) - num_computed``; remaining==1 means the next
  step computes the last token's KV and samples (decode); remaining>1 means
  a prefill chunk (which also samples iff it reaches the end).
- Prefix-cache admission: full prompt blocks are matched against the
  BlockPool by chained sequence hash (same salted-xxh3 domain as the
  frontend/router — dynamo_tpu/tokens.py), skipping their recompute.
- KV events: as blocks fill they are registered + reported stored; pool
  eviction reports removed — feeding the router's radix index exactly like
  the reference's engines do (ref: kv_router/publisher.rs).
"""

from __future__ import annotations

import itertools
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

from dynamo_tpu.engine.cache import BlockPool
from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS, EngineArgs
from dynamo_tpu.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.qos import CLASS_RANK, DEFAULT_TENANT, normalize_priority
from dynamo_tpu.qos.fair import ClassQueues, QosBook
from dynamo_tpu.router.protocols import StoredBlock
from dynamo_tpu.tokens import KV_HASH_SEED, TokenBlockSequence

logger = logging.getLogger("dynamo.engine.scheduler")

#: starvation guard for the swapped queue (docs/qos.md): a swap-in
#: candidate whose block reservation fails this many consecutive passes is
#: re-parked behind its peers (dynamo_swap_in_blocked_total counts it) so a
#: large head-of-line sequence cannot block smaller resumable ones forever
SWAP_IN_SKIP_AFTER = 3


@dataclass
class SeqState:
    request_id: str
    req: PreprocessedRequest
    ctx: object  # runtime Context (has .cancelled)
    sink: object  # asyncio.Queue for outputs (owned by engine)
    tokens: list[int] = field(default_factory=list)  # prompt + generated
    prompt_len: int = 0
    hashes: TokenBlockSequence = None
    block_table: list[int] = field(default_factory=list)
    num_computed: int = 0  # tokens whose KV is resident
    num_registered_blocks: int = 0  # blocks already registered/evented
    num_cached_prompt: int = 0  # prefix-cache hit tokens (for metrics)
    generated: int = 0
    step_idx: int = 0  # sampling step counter (PRNG determinism)
    finished: Optional[str] = None
    preemptions: int = 0
    #: disagg: keep KV blocks alive past finish (owner gathers then releases)
    hold_blocks: bool = False
    #: speculative decoding: incrementally-built n-gram → end-position index
    #: over ``tokens`` (engine._draft_tokens) — avoids O(n) history scans
    #: per decode step
    ngram_pos: dict = field(default_factory=dict)
    ngram_indexed: int = 0
    #: sampling penalties: incrementally-folded token history (engine
    #: _sample.build_triples) — ``gen_counts`` counts GENERATED tokens
    #: (presence/frequency), ``seen_tokens`` is distinct prompt+generated
    #: (repetition), ``pen_indexed`` the fold watermark into ``tokens``
    gen_counts: dict = field(default_factory=dict)
    seen_tokens: set = field(default_factory=set)
    pen_indexed: int = 0
    #: guided decoding constraint cursor (llm/guided.GuidedState), attached
    #: by the engine when the request carries guided options
    guided_state: object = None
    #: disagg pipelining: called with (num_computed) after each prefill chunk
    #: commits — lets the owner ship finished blocks while later chunks run
    progress_cb: Optional[Callable] = None
    #: preempt-to-swap: the engine's host-side swap entry while this seq's
    #: KV lives off-device (None = not swapped)
    swap: object = None
    #: per-request KV-event batching: stored blocks accumulated across
    #: prefill chunks, flushed as ONE chained event when the prompt
    #: completes (or at finish/preemption) — docs/PERF_NOTES.md fleet_bench
    pending_stored: list = field(default_factory=list)
    pending_stored_ids: list = field(default_factory=list)
    pending_parent: object = None
    #: multi-tenant QoS (docs/qos.md): tenant id + priority class copied
    #: off the Context at add() time (wire fields; absent = defaults),
    #: plus the bookkeeping the fair queues / starvation guards key on
    tenant: str = DEFAULT_TENANT
    priority: str = "standard"
    qos_enqueue_t: float = 0.0    # when the seq (re-)entered waiting
    qos_arrival: Optional[int] = None  # global arrival stamp (ClassQueues)
    swap_in_attempts: int = 0     # consecutive failed swap-in reservations
    parked_t: float = 0.0         # when the seq entered the swapped queue
    #: recurrent-state slot of a model with state layers: taken at
    #: admission, given back at finish, abort and recompute preemption
    #: (None = none held; models without state never set it)
    state_slot: Optional[int] = None
    state_waited: bool = False  # counted once in state_slot_wait_total

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.num_computed

    def sampling_tuple(self):
        s = self.req.sampling_options
        return (
            float(s.temperature if s.temperature is not None else 0.0),
            int(s.top_k if s.top_k else 0),
            float(s.top_p if s.top_p is not None else 1.0),
            s.seed,  # None = unseeded (seed=0 is a valid pinned seed)
        )


@dataclass
class PrefillWork:
    seq: SeqState
    start: int
    chunk: int  # number of tokens to compute this step
    sample: bool  # True when the chunk reaches the end of tokens


@dataclass
class StepPlan:
    #: prefill chunks batched into ONE jitted call (same-bucket rows);
    #: empty list = no prefill this step
    prefill: list[PrefillWork] = field(default_factory=list)
    decode: list[SeqState] = field(default_factory=list)

    @property
    def empty(self) -> bool:
        return not self.prefill and not self.decode


def share_prefill_budget(head: int, others: list[int], budget: int,
                         max_rows: int) -> tuple[int, list[int]]:
    """Split one step's prefill token budget among the prompts waiting for it.

    ``head`` is the oldest waiting prompt's remaining tokens, ``others`` the
    rest's in the order they are to be served (fewest remaining first).
    Half the budget, rounded up, is kept for the head; the others take
    ``min(remaining, what is left beside that)`` in turn, on at most
    ``max_rows - 1`` rows; the head then takes all that is left. Returns the
    head's chunk and the others' (a prefix of ``others``: those past it get
    nothing this step). With no others the head's chunk is ``min(head,
    budget)``, the plan of a scheduler that serves in admission order. The
    real scheduler and the mocker both size their chunks here.
    """
    if budget <= 0 or max_rows <= 0:
        return 0, []
    left = budget - min(head, (budget + 1) // 2)
    chunks: list[int] = []
    for remaining in others[:max_rows - 1]:
        if left <= 0:
            break
        chunks.append(min(remaining, left))
        left -= chunks[-1]
    return min(head, budget - sum(chunks)), chunks


class Scheduler:
    """Plans one engine iteration; owns admission/preemption/bookkeeping."""

    def __init__(self, args: EngineArgs, pool: BlockPool,
                 on_stored: Optional[Callable] = None,
                 onboard_cb: Optional[Callable] = None,
                 swapper: Optional[object] = None,
                 hot_cb: Optional[Callable] = None,
                 state_slots: int = 0):
        self.args = args
        self.pool = pool
        #: free recurrent-state slots (engine/cache.py:KvPages.state), or
        #: None for a model without state layers: nothing below looks at
        #: slots then. A sequence that starts at position 0 starts from
        #: zeros whatever its slot holds (ops/shortconv.py), so a slot is a
        #: binding only: nothing is zeroed or moved when it changes hands.
        self.state_free: Optional[list] = (
            list(range(state_slots - 1, -1, -1)) if state_slots else None)
        self.state_slots = state_slots
        #: admissions that had blocks and a row, and no slot →
        #: dynamo_state_slot_wait_total
        self.state_slot_wait_total = 0
        #: ragged-step planning (docs/performance.md), the ONLY planning
        #: mode: the step is ONE packed launch, so plan() budgets TOKENS
        #: (prefill chunks + decode rows co-scheduled under
        #: max_num_batched_tokens). Chunk sizes are free (no prefill-bucket
        #: clamp), padding-cost row checks are moot (nothing pads to a
        #: bucket), and the QoS decode sit-out collapses to plain budget
        #: accounting: better-class chunks are admitted first (class
        #: order), and decode rows cost one token each — they never
        #: inflate a better-class prefill's padded step shape, so there is
        #: nothing to shed.
        self.on_stored = on_stored  # fn(parent_hash, [StoredBlock], [block_id])
        #: fn(probe: TokenBlockSequence, start_block, end_block) -> [block_id]
        #: — KVBM onboard hook: device-misses found in host/disk tiers come
        #: back as freshly scattered device blocks extending the prefix hit
        self.onboard_cb = onboard_cb
        #: fn(probe, hit_blocks) — prefix-HIT popularity hook: the G4
        #: flow-up policy (engine._note_hot_prefix) counts repeat hits and
        #: pushes hot prefixes to the fleet-global object store
        self.hot_cb = hot_cb
        #: preempt-to-swap backend (the engine): swap_out(seq) -> bool,
        #: swap_status(seq) -> "ready"|"pending"|"failed", swap_in(seq) ->
        #: bool, swap_drop(seq). None = recompute preemption only.
        self.swapper = swapper
        #: multi-tenant QoS ledger (virtual token counters, per-tenant
        #: telemetry) + the per-class waiting queues it drains. With QoS
        #: scheduling off — or a single default tenant/class, i.e. every
        #: pre-QoS workload — the drain order is exact FIFO.
        self.qos = QosBook(args.qos)
        self.waiting: ClassQueues = ClassQueues(
            self.qos, fifo=not args.qos_scheduling)
        self.running: list[SeqState] = []
        #: swapped-out victims — between waiting and running; swap-in
        #: admission runs BEFORE _admit so a resumed sequence reclaims its
        #: old position instead of queueing behind fresh prompts. Drained
        #: best-class-first (aged sequences jump the order), FIFO within a
        #: class; plain FIFO when QoS scheduling is off.
        self.swapped: deque[SeqState] = deque()
        self._aborted: set = set()  # reaped at next plan() like cancellation
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        #: one KV stored event per REQUEST (prefill chunks accumulate on the
        #: seq and flush when the prompt completes) unless per-chunk
        #: publishing was explicitly requested
        self._batch_events = not args.kv_event_per_chunk
        # preemption telemetry (→ dynamo_preempt_{swap,recompute}_total)
        self.preempt_swap_total = 0
        self.preempt_recompute_total = 0
        self.swap_in_total = 0
        #: prompt+generated tokens thrown away by recompute preemptions —
        #: each will be re-prefilled (the waste swap-based preemption kills)
        self.recomputed_tokens_total = 0
        #: swap-in starvation guard fires (head-of-line candidate re-parked
        #: after SWAP_IN_SKIP_AFTER failed reservations) →
        #: dynamo_swap_in_blocked_total
        self.swap_in_blocked_total = 0
        #: flight-recorder signal (observability/flight.py): decode rows
        #: that were READY last plan but did not fit the step (row cap /
        #: token budget), i.e. a budget-starved decode — QoS sit-out sheds
        #: are deliberate policy and are NOT counted here
        self.last_starved_decode = 0
        #: the starved rows' request (Context) ids — the flight record's
        #: step↔request linkage, so attribution can charge the stall to
        #: the request that actually sat out (observability/attribution.py)
        self.last_starved_ids: list = []
        #: admitted sequences with prompt tokens left that got NO chunk in
        #: the last plan (budget, row cap or memory): the queue INSIDE
        #: ``running`` that ``waiting`` does not see — flight field
        #: ``prefill_blocked``
        self.last_prefill_blocked = 0
        #: chunks planned while an older prompt was left with tokens this
        #: step did not give it (fewest-first order at work) →
        #: dynamo_prefill_overtakes_total
        self.prefill_overtakes_total = 0

    # -- api ----------------------------------------------------------------

    @staticmethod
    def _salt_for(req) -> int:
        # multimodal content salts the block hashes: identical placeholder
        # tokens with different images must never share KV identity
        digest = req.mm_digest() if hasattr(req, "mm_digest") else None
        return KV_HASH_SEED if digest is None else digest

    def _stamp_qos(self, seq: SeqState) -> None:
        """Copy tenant/priority off the runtime Context (wire fields; a
        pre-QoS peer sends neither → defaults) and register the sequence
        with the fairness ledger."""
        seq.tenant = str(getattr(seq.ctx, "tenant", None)
                         or DEFAULT_TENANT)
        seq.priority = normalize_priority(
            getattr(seq.ctx, "priority", None), warn=False)
        self.qos.enter(seq)

    def add(self, seq: SeqState) -> None:
        seq.tokens = list(seq.req.token_ids)
        seq.prompt_len = len(seq.tokens)
        # PRNG step = ABSOLUTE token position, not per-seq generation
        # count: a migrated stream re-enters as prompt ‖ emitted, and the
        # tail must draw the same (seed, step) keys the unbroken run would
        # have — position-anchored steps make seeded sampling stable
        # across migration, disagg attach and recompute preemption alike
        seq.step_idx = seq.prompt_len
        seq.hashes = TokenBlockSequence(block_size=self.args.block_size,
                                        salt_hash=self._salt_for(seq.req))
        self._stamp_qos(seq)
        seq.qos_enqueue_t = time.monotonic()
        self.waiting.append(seq)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self.swapped)

    def num_waiting(self) -> int:
        return len(self.waiting)

    def plan(self) -> StepPlan:
        """Admission + the decode batch + the step's prefill chunks, sized
        and ordered by :meth:`_prefill_shares` (fewest remaining prompt
        tokens first, half the budget kept for the oldest prompt: a
        prompt's prefill takes at most twice its own steps once it is the
        oldest, whatever arrives behind it)."""
        self._reap_cancelled()
        self._swap_in_pass()
        self._admit()
        plan = StepPlan()

        budget = self.args.max_num_batched_tokens
        # row cap for BOTH batch lists: the engine pads B to a
        # decode_batch_bucket, so more rows than the largest bucket would
        # overflow the padded batch arrays
        max_b = min(self.args.max_num_seqs, self.args.decode_batch_buckets[-1])
        decode_seqs = [s for s in self.running if s.remaining == 1]
        if self.args.qos_scheduling:
            # class-ordered work within the step (docs/qos.md): interactive
            # rows claim batch budget / row slots / the prefill token
            # bucket first, so an interactive prefill chunk never pads up
            # to (or queues a step behind) a concurrent batch prompt.
            # Stable within a class — single-class workloads keep the
            # exact pre-QoS order.
            order = {id(s): i for i, s in enumerate(self.running)}
            by_class = lambda s: (CLASS_RANK.get(s.priority, 1),  # noqa: E731
                                  order[id(s)])
            decode_seqs.sort(key=by_class)

        # ensure each decode seq has a block for its last position; preempt on
        # allocation failure (victims chosen newest-first, vLLM-style).
        # _preempt_for may evict a seq we already planned, so membership in
        # self.running is re-checked before the plan is finalized.
        ready_decode = []
        for s in decode_seqs:
            if s not in self.running:
                continue  # preempted by an earlier iteration
            if self._ensure_blocks(s, s.num_computed + 1):
                ready_decode.append(s)
            else:
                if not self._preempt_for(s):
                    self._preempt(s)
        # packed step: decode rows spend the shared token budget (one
        # token each) and must also fit the packed-token bucket cap
        row_cap = min(max_b, budget)
        still_ready = [s for s in ready_decode if s in self.running]
        plan.decode = still_ready[:row_cap]
        self.last_starved_decode = len(still_ready) - len(plan.decode)
        self.last_starved_ids = [
            rid for s in still_ready[row_cap:]
            if (rid := getattr(s.ctx, "id", None))]
        budget -= len(plan.decode)

        if self.args.enable_chunked_prefill or not plan.decode:
            # several sequences' chunks ride the one packed launch; ragged
            # planning has no per-row padding, so chunks are clamped only
            # by the step's token budget and by the chunk grid, which sizes
            # for at most RAGGED_MAX_CHUNKS co-scheduled chunks
            # (model.ragged_grid_shape capacity proof)
            prefill_seqs = [s for s in self.running if s.remaining > 1]
            if self.args.qos_scheduling:
                prefill_seqs.sort(key=by_class)
            rows = min(max_b, RAGGED_MAX_CHUNKS)
            shares = (self._prefill_shares(prefill_seqs, budget, rows)
                      if self.args.enable_chunked_prefill else
                      self._whole_prompts(prefill_seqs, budget, rows))
            for s, chunk in shares:
                if s not in self.running:
                    continue  # preempted by an earlier iteration's victim pick
                protected = plan.decode + [w.seq for w in plan.prefill]
                if not self._ensure_blocks(s, s.num_computed + chunk):
                    # not enough memory: preempt, but never a seq whose
                    # block table this step's jitted calls are about to
                    # index — else wait
                    if not self._preempt_for(s, exclude=protected):
                        break
                    if not self._ensure_blocks(s, s.num_computed + chunk):
                        break
                plan.prefill.append(PrefillWork(
                    seq=s, start=s.num_computed, chunk=chunk,
                    sample=(s.num_computed + chunk == len(s.tokens)),
                ))
            # a chunk overtook if it is a younger prompt's than the oldest
            # one that this step leaves with tokens
            given = {id(w.seq): w.chunk for w in plan.prefill}
            for i, s in enumerate(prefill_seqs):
                if s in self.running and given.get(id(s), 0) < s.remaining:
                    self.prefill_overtakes_total += sum(
                        id(y) in given for y in prefill_seqs[i + 1:])
                    break
        self.last_prefill_blocked = sum(
            1 for s in self.running if s.remaining > 1) - len(plan.prefill)
        # NOTE: the bucketed planner's QoS decode sit-out (shed worse-class
        # decode rows when that shrank the compiled batch bucket) is gone
        # with the bucketed step itself: the packed ragged launch has no
        # padded batch bucket to shrink, so shedding rows would delay their
        # tokens without speeding the step by a single flop.
        return plan

    def _prefill_shares(self, seqs: list[SeqState], budget: int,
                        rows: int) -> list[tuple[SeqState, int]]:
        """This step's prefill chunks, in the order they are planned.

        ``seqs`` are the prompts with tokens left, oldest first (QoS class
        first where that is on). The oldest keeps half the budget
        (``share_prefill_budget``); the others are served fewest remaining
        tokens first, ties in admission order, each class by itself, so
        a short prompt's first token no longer waits for every chunk of a
        long prompt admitted before it. The oldest is planned first, so it
        is the first to get its blocks and is protected from the others'
        preemptions. No starvation: the oldest prompt advances by at least
        half the budget every step it has that many tokens left, so once a
        prompt is the oldest its prefill takes at most twice the steps it
        would take alone, however many shorter prompts arrive. With one
        prompt waiting, or several that all fit the budget, the chunks are
        those of the admission-order plan.
        """
        rank = ((lambda s: CLASS_RANK.get(s.priority, 1))
                if self.args.qos_scheduling else (lambda s: 0))
        out: list[tuple[SeqState, int]] = []
        for _, group in itertools.groupby(seqs, key=rank):
            # a class is a queue of its own: a worse class shares what the
            # better ones leave once every prompt of theirs is served
            head, *others = group
            others.sort(key=lambda s: s.remaining)  # stable: ties by age
            first, chunks = share_prefill_budget(
                head.remaining, [s.remaining for s in others], budget,
                rows - len(out))
            out += [(s, c) for s, c in [(head, first), *zip(others, chunks)]
                    if c]
            budget -= first + sum(chunks)
        return out

    def _whole_prompts(self, seqs: list[SeqState], budget: int,
                       rows: int) -> list[tuple[SeqState, int]]:
        """Chunked prefill off: whole prompts only, in admission order; one
        that does not fit what the budget has left waits, and a shorter one
        behind it may still fit this step."""
        out: list[tuple[SeqState, int]] = []
        for s in seqs:
            if s.remaining > self.args.max_num_batched_tokens:
                # can never fit in one unchunked step: fail it rather
                # than wedge the prefill queue forever
                self.finish(s, FinishReason.ERROR)
                s.sink.put_nowait(LLMEngineOutput(
                    finish_reason=FinishReason.ERROR,
                    text="prompt exceeds max_num_batched_tokens "
                         "and chunked prefill is disabled"))
                s.sink.put_nowait(None)
            elif s.remaining <= budget and len(out) < rows:
                out.append((s, s.remaining))
                budget -= s.remaining
        return out

    # -- post-step bookkeeping ----------------------------------------------

    def commit_computed(self, seq: SeqState, new_num_computed: int,
                        charge: bool = True) -> None:
        """Advance num_computed; hash/register/event newly-filled blocks.

        KV stored events batch PER REQUEST by default: chunks of a long
        prompt accumulate on the sequence and publish as one chained event
        when the prompt completes (decode-filled blocks still publish as
        they register — they arrive one per block_size tokens). Per-chunk
        publishing measured 11% under the 70B fleet's stored-blocks/s
        requirement; per-request has 2.3× headroom (docs/PERF_NOTES.md).
        """
        old = seq.num_computed
        seq.num_computed = new_num_computed
        # served-token accounting (docs/qos.md): every token whose KV this
        # engine computed — prefill chunks, decode steps, and recompute
        # re-prefills alike — advances the tenant's virtual counter at its
        # class weight. Prefix-cache hits and disagg-attached prompt KV
        # (charge=False) charge nothing: no work done HERE, and the prefill
        # worker already charged its own ledger, so charging again would
        # double-count dynamo_tenant_served_tokens_total fleet-wide.
        if charge:
            self.qos.charge(seq.tenant, seq.priority, new_num_computed - old)
        seq.hashes.extend(seq.tokens[len(seq.hashes): new_num_computed])
        bs = self.args.block_size
        full = new_num_computed // bs
        stored: list[StoredBlock] = []
        stored_ids: list[int] = []
        parent = None
        for i in range(seq.num_registered_blocks, full):
            blk = seq.hashes.blocks[i]
            bid = seq.block_table[i]
            fresh = self.pool.register(bid, blk.sequence_hash, blk.block_hash,
                                       blk.parent_sequence_hash)
            if fresh:
                if not stored:
                    parent = blk.parent_sequence_hash
                stored.append(StoredBlock(block_hash=blk.sequence_hash,
                                          tokens_hash=blk.block_hash))
                stored_ids.append(bid)
        seq.num_registered_blocks = full
        if not self.on_stored:
            return
        if self._batch_events and new_num_computed < seq.prompt_len:
            # mid-prompt chunk: park the delta; a later chunk (or finish/
            # preempt) flushes the whole chain in one event
            if stored:
                if not seq.pending_stored:
                    seq.pending_parent = parent
                seq.pending_stored.extend(stored)
                seq.pending_stored_ids.extend(stored_ids)
            return
        if seq.pending_stored:
            # consecutive blocks of one sequence: earlier chunks' blocks
            # chain straight into this one's, under the FIRST chunk's
            # parent. This path must run even when THIS commit registered
            # no new full block (a prompt whose tail is a partial block):
            # prompt completion is the flush point either way.
            stored = seq.pending_stored + stored
            stored_ids = seq.pending_stored_ids + stored_ids
            parent = seq.pending_parent
            seq.pending_stored, seq.pending_stored_ids = [], []
            seq.pending_parent = None
        if stored:
            self.on_stored(parent, stored, stored_ids)

    def _flush_stored(self, seq: SeqState) -> None:
        """Publish any batched-but-unflushed stored blocks. Must run BEFORE
        the seq's blocks are released (finish/preempt): the offload hook
        pins the block ids synchronously."""
        if seq.pending_stored and self.on_stored:
            self.on_stored(seq.pending_parent, seq.pending_stored,
                           seq.pending_stored_ids)
        seq.pending_stored, seq.pending_stored_ids = [], []
        seq.pending_parent = None

    def append_token(self, seq: SeqState, token: int) -> None:
        seq.tokens.append(token)
        seq.generated += 1
        seq.step_idx += 1

    def check_finish(self, seq: SeqState, token: int) -> Optional[str]:
        sc = seq.req.stop_conditions
        if not sc.ignore_eos and token in (seq.req.eos_token_ids or []):
            if (sc.min_tokens or 0) < seq.generated:
                return FinishReason.EOS
        gs = seq.guided_state
        if gs is not None and (gs.done or gs.exhausted):
            # constraint completed (or hit a token-level dead end): stop
            # even without EOS ids / with ignore_eos — free-running past
            # the constraint would emit unconstrained tokens. min_tokens
            # delays only the DONE stop; an exhausted machine has every
            # next token masked, so it must stop regardless
            if gs.exhausted or (sc.min_tokens or 0) <= seq.generated:
                return FinishReason.STOP
        if sc.max_tokens is not None and seq.generated >= sc.max_tokens:
            return FinishReason.LENGTH
        if seq.num_computed + 1 >= self.args.max_model_len:
            return FinishReason.LENGTH
        return None

    def finish(self, seq: SeqState, reason: str) -> None:
        seq.finished = reason
        self.qos.leave(seq)
        if seq.guided_state is not None:
            # structured decoding: drop the seq's device-FSM arena
            # reference so idle constraint tables become evictable
            # (duck-typed — the host oracle has no release)
            rel = getattr(seq.guided_state, "release", None)
            if rel is not None:
                rel()
        self._flush_stored(seq)
        if seq in self.running:
            self.running.remove(seq)
        self._drop_state_slot(seq)
        if seq.swap is not None and self.swapper is not None:
            self.swapper.swap_drop(seq)
        if not seq.hold_blocks:
            self.pool.release(seq.block_table)
            seq.block_table = []

    def _note_slot_wait(self, seq: SeqState) -> None:
        """``seq`` could not be admitted: counted, once a sequence, if what
        it lacks is a state slot (every slot, one a row, is held) and not
        blocks."""
        if (not self.state_free and not seq.state_waited
                and self.pool.num_free_blocks
                > max(1, self.args.watermark * self.pool.num_blocks)):
            seq.state_waited = True
            self.state_slot_wait_total += 1

    def _drop_state_slot(self, seq: SeqState) -> None:
        if seq.state_slot is not None:
            self.state_free.append(seq.state_slot)
            seq.state_slot = None

    def release_held(self, seq: SeqState) -> None:
        """Free the blocks of a finished hold_blocks sequence."""
        self.pool.release(seq.block_table)
        seq.block_table = []

    def add_prefilled(self, seq: SeqState, block_table: list[int]) -> None:
        """Admit a sequence whose prompt KV was computed elsewhere (disagg:
        decode worker receives prefill's pages already scattered into
        ``block_table``). Registers/hashes the prompt blocks so prefix cache
        and KV events behave exactly as if prefill ran locally."""
        seq.tokens = list(seq.req.token_ids)
        self._stamp_qos(seq)
        seq.prompt_len = len(seq.tokens)
        seq.step_idx = seq.prompt_len  # position-anchored PRNG (see add())
        seq.hashes = TokenBlockSequence(block_size=self.args.block_size,
                                        salt_hash=self._salt_for(seq.req))
        seq.block_table = list(block_table)
        self.running.append(seq)
        # charge=False: the prompt's KV was computed (and QoS-charged) on
        # the prefill worker; this engine only attaches the pages
        self.commit_computed(seq, seq.prompt_len, charge=False)

    # -- internals -----------------------------------------------------------

    def abort(self, seq: SeqState) -> None:
        """Owner vanished (e.g. prefill_extract cancelled): guarantee the
        seq's blocks return to the pool no matter what state it is in."""
        if seq.finished is not None:
            if seq.block_table:
                self.release_held(seq)
            return
        seq.hold_blocks = False  # eventual finish() must release
        self._aborted.add(id(seq))

    def _reap_cancelled(self) -> None:
        def dead(s):
            return getattr(s.ctx, "cancelled", False) or id(s) in self._aborted

        def expired(s):
            # end-to-end deadline (runtime Context): enforced at PLAN time so
            # an expired sequence never spends another device step
            return getattr(s.ctx, "expired", False)

        for s in list(self.running):
            if dead(s):
                self._aborted.discard(id(s))
                self.finish(s, FinishReason.CANCELLED)
                s.sink.put_nowait(None)  # unblock the generate() consumer
            elif expired(s):
                self.finish(s, FinishReason.DEADLINE)
                s.sink.put_nowait(LLMEngineOutput(
                    finish_reason=FinishReason.DEADLINE))
        for s in list(self.waiting):
            if dead(s):
                self._aborted.discard(id(s))
                s.finished = FinishReason.CANCELLED
                self.waiting.remove(s)
                self.qos.leave(s)
                s.sink.put_nowait(None)
            elif expired(s):
                s.finished = FinishReason.DEADLINE
                self.waiting.remove(s)
                self.qos.leave(s)
                s.sink.put_nowait(LLMEngineOutput(
                    finish_reason=FinishReason.DEADLINE))
        for s in list(self.swapped):
            # cancel-safe teardown: a swapped seq holds NO device blocks,
            # only a host bundle + budget reservation — drop both
            if dead(s) or expired(s):
                self._aborted.discard(id(s))
                self.swapped.remove(s)
                self.qos.leave(s)
                if self.swapper is not None:
                    self.swapper.swap_drop(s)
                if dead(s):
                    s.finished = FinishReason.CANCELLED
                    s.sink.put_nowait(None)
                else:
                    s.finished = FinishReason.DEADLINE
                    s.sink.put_nowait(LLMEngineOutput(
                        finish_reason=FinishReason.DEADLINE))

    def _swap_in_candidate(self, exclude: frozenset = frozenset()) -> SeqState:
        """Next swapped sequence to resume: aged ones first (oldest parked,
        starvation guard), then best class, then FIFO by park time. Plain
        FIFO when QoS scheduling is off.

        ``exclude`` holds ids of candidates already re-parked THIS pass:
        without it the class-first order re-picks a sole best-class
        candidate immediately after its own skip-ahead (re-parking only
        moves it behind same-class peers), and worse-class sequences
        behind it are never even tried."""
        if not self.args.qos_scheduling:
            return self.swapped[0]
        pool = [s for s in self.swapped if id(s) not in exclude] \
            or list(self.swapped)
        now = time.monotonic()
        aging = self.qos.cfg.aging_s
        if aging > 0:
            aged = [s for s in pool if now - s.parked_t >= aging]
            if aged:
                return min(aged, key=lambda s: s.parked_t)
        return min(pool,
                   key=lambda s: (CLASS_RANK.get(s.priority, 1), s.parked_t))

    def _swap_in_fallback(self, seq: SeqState) -> None:
        """Swap-in impossible (torn bundle / failed copy): resolve the
        preemption by recompute. Counted as recompute even though the
        swap-out counted as swap — or dashboards read 100% swap success
        while recomputed tokens climb."""
        self.preempt_recompute_total += 1
        self.recomputed_tokens_total += seq.num_computed
        self._reset_for_recompute(seq)
        seq.qos_enqueue_t = time.monotonic()
        self.waiting.appendleft(seq)

    def _swap_in_pass(self) -> None:
        """Re-activate swapped-out sequences when capacity returns.

        Swap-in admission charges ``_ensure_blocks`` for the sequence's
        whole resident prefix BEFORE re-activation (plus one token of
        headroom so the imminent decode/prefill step cannot immediately
        re-preempt it), and runs before ``_admit`` so a resumed sequence
        takes priority over fresh prompts — it resumes at its old progress
        instead of re-prefilling behind the queue.

        Starvation guard (docs/qos.md): a head-of-line candidate whose
        block reservation keeps failing — e.g. a long sequence needing more
        blocks than ever free at once — is re-parked behind its peers after
        ``SWAP_IN_SKIP_AFTER`` failed passes (``dynamo_swap_in_blocked_total``
        counts each re-park) so smaller resumable sequences get their shot.
        """
        if self.swapper is None:
            return
        rotations = 0
        skipped: set = set()  # re-parked this pass: don't re-pick them
        while self.swapped and len(self.running) < self.args.max_num_seqs:
            if rotations > len(self.swapped):
                break  # full cycle without progress: wait for more memory
            seq = self._swap_in_candidate(frozenset(skipped))
            st = self.swapper.swap_status(seq)
            if st == "pending":
                break  # host copy still in flight; order preserved
            if st != "ready":
                # bundle torn down / copy failed: recompute fallback
                self.swapped.remove(seq)
                logger.warning("swap-in of %s unavailable (%s); falling "
                               "back to recompute", seq.request_id, st)
                self.swapper.swap_drop(seq)  # reclaim budget/accounting
                self._swap_in_fallback(seq)
                continue
            bs = self.args.block_size
            need = (seq.num_computed + bs) // bs  # ceil((computed+1)/bs)
            free_after = self.pool.num_free_blocks - need
            watermarked = (self.running and self.pool.num_free_blocks - need
                           < self.args.watermark * self.pool.num_blocks)
            if free_after < 0 or watermarked:
                # not enough room for THIS candidate. A smaller sequence
                # behind it may still fit: after SWAP_IN_SKIP_AFTER failed
                # passes the candidate is re-parked (skip-ahead) instead of
                # pinning the whole queue behind its reservation.
                seq.swap_in_attempts += 1
                if (len(self.swapped) > 1
                        and seq.swap_in_attempts >= SWAP_IN_SKIP_AFTER):
                    seq.swap_in_attempts = 0
                    seq.parked_t = time.monotonic()  # back of its class
                    self.swapped.remove(seq)  # and of the FIFO order
                    self.swapped.append(seq)
                    skipped.add(id(seq))  # let worse classes have a shot
                    self.swap_in_blocked_total += 1
                    rotations += 1
                    logger.info("swap-in of %s blocked (needs %d blocks, "
                                "%d free); skipping ahead", seq.request_id,
                                need, self.pool.num_free_blocks)
                    continue
                break  # wait, don't thrash
            self.swapped.remove(seq)
            if not self._ensure_blocks(seq, seq.num_computed + 1):
                seq.swap_in_attempts += 1
                self.swapped.appendleft(seq)
                break
            seq.swap_in_attempts = 0
            if not self.swapper.swap_in(seq):
                self.pool.release(seq.block_table)
                seq.block_table = []
                self._swap_in_fallback(seq)  # resolved by recompute
                continue
            self.swap_in_total += 1
            # old position: ahead of every later admission, and victim
            # selection (newest-first) reaches it last
            self.running.insert(0, seq)

    def _make_room_for(self, seq: SeqState) -> bool:
        """Admission-time priority preemption (docs/qos.md): evict one
        running sequence of a STRICTLY worse class — lowest class /
        highest debt / newest first, through the swap path when the host
        budget allows — so an arriving higher-priority request gets its
        slot and blocks now instead of queueing behind saturated batch
        work. Same-class running work is never churned. False = no
        eligible victim (the arrival waits like before)."""
        if not self.args.qos_scheduling:
            return False
        rank = CLASS_RANK.get(seq.priority, 1)
        for victim in self._victim_order(seq):
            if CLASS_RANK.get(victim.priority, 1) <= rank:
                continue
            self._preempt(victim)
            return True
        return False

    def _admit(self) -> None:
        bs = self.args.block_size
        now = time.monotonic()
        while self.waiting:
            # weighted-fair pick (docs/qos.md): the backlogged tenant with
            # the least virtual time goes first (aging escape hatch for
            # starving sequences; exact FIFO with QoS scheduling off or a
            # single default tenant/class)
            seq = self.waiting.pick(now)
            # slots full: a higher-priority arrival may claim one from a
            # worse-class victim; anything else waits. The freed capacity
            # goes to THIS seq, not a re-pick — a recompute-preempted
            # victim lands back in waiting with a lower virtual time than
            # the arrival that displaced it, and a re-pick would hand it
            # straight back its old slot and preempt it again, forever.
            # _make_room_for only ever evicts strictly-worse classes, so
            # each call shrinks running and the loop is bounded.
            while len(self.running) >= self.args.max_num_seqs:
                if not self._make_room_for(seq):
                    if self.state_free is not None:
                        self._note_slot_wait(seq)
                    return
            # watermark: keep a fraction of blocks free (ref: mocker watermark)
            needed_first = max(1, min(len(seq.tokens), bs) // bs + 1)
            while (self.pool.num_free_blocks < needed_first
                   or (self.running and self.pool.num_free_blocks
                       < self.args.watermark * self.pool.num_blocks)):
                if not self._make_room_for(seq):
                    return
            if self.state_free is not None:
                if not self.state_free:
                    self._note_slot_wait(seq)
                    return
                seq.state_slot = self.state_free.pop()
            self.waiting.remove(seq)
            self.qos.note_queue_wait(seq.tenant, seq.priority,
                                     max(0.0, now - seq.qos_enqueue_t))
            if seq.num_computed == 0 and not seq.block_table:
                self._prefix_match(seq)
            self.running.append(seq)

    def _prefix_match(self, seq: SeqState) -> None:
        self.prefix_query_tokens += seq.prompt_len
        if not self.args.enable_prefix_caching:
            return
        bs = self.args.block_size
        # match only full *prompt* blocks, and never the whole prompt — at
        # least one token must be computed to produce logits
        matchable = (seq.prompt_len - 1) // bs
        if matchable <= 0:
            return
        # the probe MUST use the same salt as registration: an unsalted
        # probe would let a multimodal request reuse KV computed for the
        # same tokens WITHOUT its image embeddings (and vice versa)
        probe = TokenBlockSequence.from_tokens(
            seq.tokens[: matchable * bs], bs, self._salt_for(seq.req))
        hit_blocks = self.pool.match_prefix(probe.sequence_hashes())
        if self.onboard_cb is not None and len(hit_blocks) < matchable:
            hit_blocks = hit_blocks + self.onboard_cb(
                probe, len(hit_blocks), matchable)
        if not hit_blocks:
            return
        n = len(hit_blocks)
        if self.hot_cb is not None:
            # popularity signal for the G4 prefix flow-up: this leading
            # run was just re-used (never fired on the cold first compute)
            self.hot_cb(probe, n)
        seq.block_table = list(hit_blocks)
        seq.num_computed = n * bs
        seq.num_cached_prompt = n * bs
        seq.num_registered_blocks = n
        seq.hashes.extend(seq.tokens[: n * bs])
        self.prefix_hit_tokens += n * bs

    def _ensure_blocks(self, seq: SeqState, target_tokens: int) -> bool:
        bs = self.args.block_size
        need = (target_tokens + bs - 1) // bs - len(seq.block_table)
        if need <= 0:
            return True
        got = self.pool.allocate(need)
        if got is None:
            return False
        seq.block_table.extend(got)
        return True

    def _preempt_for(self, needy: SeqState, exclude=()) -> bool:
        """Preempt another running seq to free memory. True if any.

        Victim order under QoS (docs/qos.md): lowest priority class first
        (batch before standard before interactive), then the tenant with
        the most accumulated service (highest virtual time — the "debt"
        that weighted fairness says should yield first), then newest. A
        victim of a BETTER class than the needy sequence is never taken —
        the needy one preempts itself instead (caller falls through to
        ``_preempt(needy)``), which is exactly how interactive KV survives
        batch pressure. With QoS scheduling off: newest-first, vLLM-style.

        ``exclude`` protects sequences already finalized into this step's
        decode batch: evicting one would free the very block table the
        imminent jitted call is about to index (the bench-on-TPU crash —
        a prefill chunk preempting a planned decode mid-step).
        """
        for victim in self._victim_order(needy):
            if victim is needy or any(victim is e for e in exclude):
                continue
            self._preempt(victim)
            return True
        return False

    def _victim_order(self, needy: SeqState) -> list[SeqState]:
        if not self.args.qos_scheduling:
            return list(reversed(self.running))
        needy_rank = CLASS_RANK.get(needy.priority, 1)
        idx = {id(s): i for i, s in enumerate(self.running)}
        candidates = [s for s in self.running
                      if CLASS_RANK.get(s.priority, 1) >= needy_rank]
        return sorted(
            candidates,
            key=lambda s: (CLASS_RANK.get(s.priority, 1),
                           self.qos.vt_of(s.tenant), idx[id(s)]),
            reverse=True)

    def _preempt(self, seq: SeqState) -> None:
        """Evict a victim to free KV blocks: swap its resident pages to the
        host tier when the swapper accepts (budget available), else the
        classic release-and-recompute. Either way the victim's device
        blocks return to the pool THIS plan — the swap gather is dispatched
        against the immutable current cache array before release."""
        self._flush_stored(seq)  # blocks are still resident: pinnable
        if (self.swapper is not None and seq.num_computed > 0
                and seq.block_table and self.swapper.swap_out(seq)):
            logger.info("preempting request %s (swap-out, %d tokens)",
                        seq.request_id, seq.num_computed)
            self.pool.release(seq.block_table)
            seq.block_table = []
            seq.preemptions += 1
            self.preempt_swap_total += 1
            self.qos.note_preempt(seq.tenant, seq.priority)
            if seq in self.running:
                self.running.remove(seq)
            seq.parked_t = time.monotonic()
            seq.swap_in_attempts = 0
            self.swapped.append(seq)
            return
        if seq.num_computed > 0:
            self.qos.note_preempt(seq.tenant, seq.priority)
            # a zero-progress victim (admitted, nothing computed) discards
            # no KV — requeueing it is free and counts as neither a swap
            # nor a recompute preemption
            logger.warning("preempting request %s (recompute)",
                           seq.request_id)
            self.preempt_recompute_total += 1
            self.recomputed_tokens_total += seq.num_computed
        self.pool.release(seq.block_table)
        seq.block_table = []
        self._drop_state_slot(seq)  # dropped, not swapped: recompute
        self._reset_for_recompute(seq)
        seq.preemptions += 1
        if seq in self.running:
            self.running.remove(seq)
        seq.qos_enqueue_t = time.monotonic()
        self.waiting.appendleft(seq)

    def _reset_for_recompute(self, seq: SeqState) -> None:
        """Zero a sequence's computed-KV bookkeeping so admission re-runs
        its prefill from scratch (the recompute-preemption path)."""
        seq.num_computed = 0
        seq.num_registered_blocks = 0
        seq.num_cached_prompt = 0
        seq.hashes = TokenBlockSequence(block_size=self.args.block_size,
                                        salt_hash=self._salt_for(seq.req))
