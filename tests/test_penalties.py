"""Sampling penalties: presence/frequency (OpenAI) + repetition (nvext/HF).

Ref surface: the reference's sampling options carry all three through to
its engines (lib/llm/src/protocols/common.rs; nvext repetition_penalty in
lib/async-openai/src/types/nvext.rs) — here they are applied as sparse
logit edits in AsyncJaxEngine._sample.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine, _has_penalties
from dynamo_tpu.engine.scheduler import SeqState
from dynamo_tpu.protocols import PreprocessedRequest, SamplingOptions


def _req(tokens, **sampling):
    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        sampling_options=SamplingOptions(temperature=0.0, **sampling))


def _seq(req, tokens, prompt_len):
    s = SeqState(request_id="r0", req=req, ctx=None, sink=None)
    s.tokens = list(tokens)
    s.prompt_len = prompt_len
    return s


@pytest.fixture(scope="module")
def engine():
    eng = AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(
        block_size=16, num_blocks=64, max_num_seqs=4,
        max_num_batched_tokens=128, max_model_len=128))
    yield eng
    asyncio.run(eng.close())


def _sample_one(engine, seq, logits_row):
    logits = np.asarray([logits_row], np.float32)
    toks, _, _ = asyncio.run(engine._sample([seq], logits))
    return int(toks[0])


def test_no_penalty_is_plain_argmax(engine):
    seq = _seq(_req([1, 2]), [1, 2, 3], prompt_len=2)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 3
    assert not _has_penalties(seq)


def test_presence_penalty_demotes_generated_tokens(engine):
    # token 3 was generated (prompt_len=2, tokens=[1,2,3]); presence=4
    # drops its logit 5.0 -> 1.0 ([0,1,2,5,0] -> [0,1,2,1,0]), so argmax
    # moves to token 2
    seq = _seq(_req([1, 2], presence_penalty=4.0), [1, 2, 3], prompt_len=2)
    assert _has_penalties(seq)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 2


def test_presence_ignores_prompt_tokens(engine):
    # token 3 is in the PROMPT, nothing generated yet — OpenAI presence
    # penalty counts only generated text, so argmax is unchanged
    seq = _seq(_req([1, 2, 3], presence_penalty=4.0), [1, 2, 3], prompt_len=3)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 3


def test_frequency_penalty_scales_with_count(engine):
    # token 3 generated twice: 5.0 - 2*2.0 = 1.0 < 2.0 -> argmax 2
    seq = _seq(_req([1], frequency_penalty=2.0), [1, 3, 3], prompt_len=1)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 2
    # generated once: 5.0 - 2.0 = 3.0 still wins
    seq = _seq(_req([1], frequency_penalty=2.0), [1, 3], prompt_len=1)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 3


def test_repetition_penalty_hf_semantics(engine):
    # HF: over prompt+generated; logit>0 -> /p, logit<0 -> *p
    # tokens seen: {1, 3}. row [-1, 4, 2.5, 6, 0], p=3:
    #   token 1: 4/3 = 1.33, token 3: 6/3 = 2.0 -> argmax token 2 (2.5)
    seq = _seq(_req([1, 3], repetition_penalty=3.0), [1, 3], prompt_len=2)
    assert _has_penalties(seq)
    assert _sample_one(engine, seq, [-1.0, 4.0, 2.5, 6.0, 0.0]) == 2
    # negative logits get MORE negative: token 0 at -1 -> -3
    seq = _seq(_req([0], repetition_penalty=3.0), [0], prompt_len=1)
    r = _sample_one(engine, seq, [-1.0, -2.5, -9.0, -9.0, -9.0])
    assert r == 1  # -2.5 now beats -3.0


def test_repetition_one_is_neutral(engine):
    seq = _seq(_req([3], repetition_penalty=1.0), [3], prompt_len=1)
    assert not _has_penalties(seq)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 3


def test_penalties_compose_with_logit_bias(engine):
    # bias +10 on token 0 outweighs everything; presence demotes token 3
    seq = _seq(_req([1], presence_penalty=4.0, logit_bias={0: 10.0}),
               [1, 3], prompt_len=1)
    assert _sample_one(engine, seq, [0.0, 1.0, 2.0, 5.0, 0.0]) == 0


@pytest.mark.slow
@pytest.mark.anyio
async def test_e2e_presence_penalty_forbids_repeats():
    """Greedy decode on random weights repeats tokens; an overwhelming
    presence penalty must make every generated token distinct — and the
    request must NOT take the fused burst path (which can't apply it)."""
    from dynamo_tpu.protocols import StopConditions
    from dynamo_tpu.runtime.context import Context

    cfg = ModelConfig.tiny()
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=16, num_blocks=64, max_num_seqs=4,
        max_num_batched_tokens=128, max_model_len=128,
        multi_step_decode=4))
    try:
        async def run(penalty):
            req = PreprocessedRequest(
                model="tiny", token_ids=[1, 2, 3, 4],
                sampling_options=SamplingOptions(
                    temperature=0.0, presence_penalty=penalty),
                stop_conditions=StopConditions(max_tokens=12, ignore_eos=True))
            out = []
            async for o in eng.generate(req, Context()):
                out.extend(o.token_ids)
            return out

        toks = await run(100.0)
        assert len(toks) == 12
        assert len(set(toks)) == len(toks), f"repeats under penalty: {toks}"
        base = await run(0.0)
        assert len(set(base)) < len(base), "tiny greedy model should repeat"
    finally:
        await eng.close()
