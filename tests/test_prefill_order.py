"""The order in which a step's prefill token budget is handed out (ISSUE 34):
fewest remaining prompt tokens first, half the budget kept for the oldest
prompt. ``plan()`` is driven directly, as ``tests/test_qos.py`` drives it;
one engine-level test holds that the order changes no request's tokens.
"""

import asyncio
import itertools

import pytest

from dynamo_tpu.engine.cache import BlockPool
from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS, EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.engine.scheduler import (
    Scheduler, SeqState, share_prefill_budget,
)
from dynamo_tpu.protocols import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)

BS = 16
BUDGET = 1024


class _Ctx:
    cancelled = False
    expired = False

    def __init__(self, priority="standard"):
        self.tenant = "t"
        self.priority = priority
        self.id = None


class _Sink:
    def put_nowait(self, item):
        pass


_counter = itertools.count()


def _seq(isl, priority="standard"):
    req = PreprocessedRequest(
        model="t", token_ids=list(range(1, isl + 1)),
        stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
    return SeqState(request_id=f"r{next(_counter)}-{isl}", req=req,
                    ctx=_Ctx(priority), sink=_Sink())


def _sched(num_blocks=4096, qos_scheduling=False, **kw):
    args = EngineArgs(block_size=BS, num_blocks=num_blocks, max_num_seqs=64,
                      max_num_batched_tokens=BUDGET, max_model_len=8192,
                      enable_prefix_caching=False, preempt_swap=False,
                      qos_scheduling=qos_scheduling, **kw)
    return Scheduler(args, BlockPool(num_blocks, False))


def _admission_order(self, seqs, budget, rows):
    """The plan before ISSUE 34: ``min(remaining, budget)`` each, in
    admission order, until the budget or the rows run out."""
    out = []
    for s in seqs[:rows]:
        if budget <= 0:
            break
        out.append((s, min(s.remaining, budget)))
        budget -= out[-1][1]
    return out


def _step(sched, osl=4):
    """One plan, serviced synchronously (commit, sample, finish)."""
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
            sched.finish(s, "length")
    return plan


def _chunks(plan):
    return [(w.seq.prompt_len, w.start, w.chunk, w.sample)
            for w in plan.prefill]


def case_one_prefill_is_the_old_plan(monkeypatch):
    """With one prompt waiting at a time (beside decode rows) every plan is
    the admission-order plan's, chunk for chunk."""
    def run(patch):
        with monkeypatch.context() as m:
            if patch:
                m.setattr(Scheduler, "_prefill_shares", _admission_order)
            sched = _sched()
            plans = []
            for isl in (3000, 17, 1024, 2500):
                sched.add(_seq(isl))
                while any(s.remaining > 1 for s in sched.running) \
                        or sched.waiting:
                    plan = _step(sched, osl=6)
                    plans.append((_chunks(plan), len(plan.decode)))
            assert sched.prefill_overtakes_total == 0
            return plans

    new, old = run(False), run(True)
    assert new == old and len(new) >= 9


def case_short_prompts_pass_a_long_head(monkeypatch):
    sched = _sched()
    decoding = _seq(40)
    sched.add(decoding)
    _step(sched)                      # one decode row rides every step
    for isl in (3072, 67, 576, 756):
        sched.add(_seq(isl))
    plan = sched.plan()
    got = {w.seq.prompt_len: w.chunk for w in plan.prefill}
    budget = BUDGET - len(plan.decode)
    assert len(plan.decode) == 1
    assert got[3072] >= budget // 2
    assert got[67] == 67              # fewest first: whole, and sampled
    assert got[576] == budget - got[3072] - 67
    assert 756 not in got             # the budget ran out before the longest
    assert sum(got.values()) + len(plan.decode) == BUDGET
    assert [w.seq.prompt_len for w in plan.prefill] == [3072, 67, 576]
    assert sched.last_prefill_blocked == 1
    assert sched.prefill_overtakes_total == 2
    # all that fit the budget: the admission-order plan's chunks
    sched2 = _sched()
    for isl in (300, 100, 200):
        sched2.add(_seq(isl))
    plan2 = sched2.plan()
    assert {w.seq.prompt_len: w.chunk for w in plan2.prefill} == {
        300: 300, 100: 100, 200: 200}
    assert sched2.last_prefill_blocked == 0
    assert sched2.prefill_overtakes_total == 0


def case_no_starvation(monkeypatch):
    """A 4,096-token head under an endless stream of 64-token arrivals, as
    many a step as the rows allow: at most twice its own four steps."""
    sched = _sched()
    head = _seq(4096)
    sched.add(head)
    steps = 0
    while head.remaining > 1:
        for _ in range(RAGGED_MAX_CHUNKS + 2):
            sched.add(_seq(64))
        plan = _step(sched, osl=1)
        assert sum(w.chunk for w in plan.prefill) + len(plan.decode) <= BUDGET
        steps += 1
        assert steps <= 8
    assert steps > 4                  # the arrivals did take their share
    assert sched.prefill_overtakes_total > 0


def case_class_is_the_outer_key(monkeypatch):
    """``qos_scheduling`` on: a batch-class short prompt never gets a chunk
    in a step that leaves an interactive-class prompt short of tokens."""
    sched = _sched(qos_scheduling=True)
    batch = _seq(64, "batch")
    sched.add(batch)
    for isl in (3072, 2048, 70):
        sched.add(_seq(isl, "interactive"))
    steps_before_batch = 0
    while batch.remaining > 1:
        plan = sched.plan()
        given = {id(w.seq): w.chunk for w in plan.prefill}
        left_short = [s for s in sched.running
                      if s.priority == "interactive" and s.remaining > 1
                      and given.get(id(s), 0) < s.remaining]
        if id(batch) in given:
            assert not left_short
        else:
            assert left_short
            steps_before_batch += 1
        if steps_before_batch == 1 and id(batch) not in given:
            # within the class the 70-token prompt passes the 2,048 one
            assert [w.seq.prompt_len for w in plan.prefill] == [3072, 70, 2048]
        for w in plan.prefill:
            sched.commit_computed(w.seq, w.start + w.chunk)
            if w.sample:
                sched.finish(w.seq, "length")
    assert steps_before_batch == 5    # 5,190 interactive tokens, 1,024 a step


def case_row_cap(monkeypatch):
    sched = _sched()
    for isl in (3072, 20, 21, 22, 23, 24, 25):
        sched.add(_seq(isl))
    plan = sched.plan()
    assert len(plan.prefill) == RAGGED_MAX_CHUNKS
    assert [w.seq.prompt_len for w in plan.prefill] == [3072, 20, 21, 22]
    assert plan.prefill[0].chunk == BUDGET - 20 - 21 - 22
    assert sched.last_prefill_blocked == 3


def case_allocation_failure_keeps_the_head(monkeypatch):
    """The pool holds the head's chunk and the first short prompt's, not the
    second's: the head's chunk stays planned and nothing is preempted."""
    sched = _sched(num_blocks=4096)
    for isl in (3072, 64, 128):
        sched.add(_seq(isl))
    head_chunk, _ = share_prefill_budget(3072, [64, 128], BUDGET, 4)
    keep = -(-head_chunk // BS) + 64 // BS
    taken = sched.pool.allocate(sched.pool.num_free_blocks - keep)
    assert taken is not None
    plan = sched.plan()
    assert [(w.seq.prompt_len, w.chunk) for w in plan.prefill] == [
        (3072, head_chunk), (64, 64)]
    assert sched.last_prefill_blocked == 1
    assert sched.preempt_recompute_total == 0
    assert len(sched.running) == 3


CASES = [case_one_prefill_is_the_old_plan,
         case_short_prompts_pass_a_long_head,
         case_no_starvation,
         case_class_is_the_outer_key,
         case_row_cap,
         case_allocation_failure_keeps_the_head]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_prefill_order(case, monkeypatch):
    case(monkeypatch)


@pytest.mark.parametrize("head,others,budget,rows,want", [
    (3072, [], 1024, 4, (1024, [])),
    (3072, [67, 576, 756], 1024, 4, (512, [67, 445])),
    (300, [100, 200], 1024, 4, (300, [100, 200])),
    (3072, [10, 10, 10, 10], 1024, 4, (994, [10, 10, 10])),
    (3072, [5], 1, 4, (1, [])),
    (3072, [5], 0, 4, (0, [])),
    (100, [900, 900], 1023, 4, (100, [900, 23])),
])
def test_share_prefill_budget(head, others, budget, rows, want):
    got = share_prefill_budget(head, others, budget, rows)
    assert got == want
    assert got[0] + sum(got[1]) <= max(budget, 0)
    assert got[0] >= min(head, (budget + 1) // 2) or budget <= 0


@pytest.mark.anyio
async def test_token_streams_do_not_depend_on_the_order(monkeypatch):
    """One long prompt and four short ones behind it, greedy: the same token
    streams whether the budget goes out in admission order (the plan before
    ISSUE 34) or fewest-first; only the second lets a chunk overtake."""
    prompts = [[(7 * i + 3) % 200 + 1 for i in range(150)],
               [(5 * i + 1) % 200 + 1 for i in range(9)],
               [(3 * i + 2) % 200 + 1 for i in range(40)],
               [(11 * i + 5) % 200 + 1 for i in range(23)],
               [(13 * i + 7) % 200 + 1 for i in range(17)]]

    async def run():
        eng = AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(
            block_size=4, num_blocks=256, max_num_seqs=8,
            max_num_batched_tokens=32, max_model_len=256,
            prefill_buckets=(8, 16, 32), decode_batch_buckets=(1, 2, 4, 8),
            enable_prefix_caching=False))

        async def one(p):
            r = PreprocessedRequest(
                model="tiny", token_ids=p,
                stop_conditions=StopConditions(max_tokens=10,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            toks = []
            async for out in eng.generate(r, None):
                toks.extend(out.token_ids)
            return toks

        streams = await asyncio.gather(*[one(p) for p in prompts])
        overtakes = eng.scheduler.prefill_overtakes_total
        blocked = [r["prefill_blocked"] for r in eng.flight.snapshot()
                   if r["kind"] == "ragged"]
        await eng.close()
        # the flight records carry the queue inside ``running``, every step
        assert max(blocked) >= 1 and blocked[-1] == 0
        return streams, overtakes

    new, overtakes_new = await run()
    monkeypatch.setattr(Scheduler, "_prefill_shares", _admission_order)
    old, overtakes_old = await run()
    assert new == old
    assert all(len(t) == 10 for t in new)
    assert overtakes_new > 0 and overtakes_old == 0
