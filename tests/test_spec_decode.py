"""Prompt-lookup speculative decoding: greedy invariance + acceptance.

The engine drafts tokens from the sequence's own history and verifies them
in one forward (ref surface: SpecDecodeStats, kv_router/protocols.rs:48-84 —
the reference delegates the mechanism to its engines; here it is native).
The hard guarantee: greedy outputs are IDENTICAL with spec decode on or off.
"""

import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    OutputOptions, PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


def make_engine(**kw) -> AsyncJaxEngine:
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=4,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64),
                    decode_batch_buckets=(1, 2, 4))
    defaults.update(kw)
    return AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(**defaults))


async def run(eng, prompt, max_tokens=16, temperature=0.0, logprobs=None):
    req = PreprocessedRequest(
        model="t", token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature),
        output_options=OutputOptions(logprobs=logprobs))
    toks = []
    async for out in eng.generate(req):
        toks.extend(out.token_ids)
    return toks


def test_draft_tokens_prompt_lookup():
    from types import SimpleNamespace

    def d(tokens, k):
        s = SimpleNamespace(tokens=tokens, ngram_pos={}, ngram_indexed=0)
        return AsyncJaxEngine._draft_tokens(s, k)

    # trailing [5,6] seen earlier → continuation [7,8,9]
    assert d([1, 5, 6, 7, 8, 9, 2, 5, 6], 3) == [7, 8, 9]
    # newest match wins
    assert d([5, 6, 1, 5, 6, 2, 9, 5, 6], 2) == [2, 9]
    # nothing repeats → no draft
    assert d([1, 2, 3, 4, 5], 3) == []
    assert d([7], 3) == []

    # incremental: the index extends as the sequence grows, and the
    # trailing gram never matches itself
    s = SimpleNamespace(tokens=[1, 5, 6, 7], ngram_pos={}, ngram_indexed=0)
    assert AsyncJaxEngine._draft_tokens(s, 2) == []
    s.tokens = s.tokens + [2, 5, 6]
    assert AsyncJaxEngine._draft_tokens(s, 2) == [7, 2]


async def test_greedy_invariance_repetitive_prompt():
    """A repetitive prompt gets drafts ACCEPTED — and the token stream must
    equal plain greedy decode exactly."""
    phrase = [11, 12, 13, 14, 15, 16]
    prompt = phrase * 4  # heavy n-gram structure
    plain = make_engine()
    spec = make_engine(speculative_tokens=4)

    want = await run(plain, prompt, max_tokens=20)
    got = await run(spec, prompt, max_tokens=20)
    assert got == want
    assert spec.spec_stats.num_drafts > 0
    assert spec.spec_stats.num_accepted_tokens > 0
    # spec needed fewer dispatches than tokens (the point of the feature)
    assert spec.spec_stats.num_spec_tokens > spec.spec_stats.num_drafts
    await plain.close()
    await spec.close()


async def test_greedy_invariance_random_prompt():
    """Non-repetitive prompts (drafts mostly rejected/absent) must also be
    byte-identical — rejections may not corrupt the cache."""
    prompt = [7, 91, 23, 151, 3, 88, 42, 199, 64, 5, 130, 77]
    plain = make_engine()
    spec = make_engine(speculative_tokens=4)
    want = await run(plain, prompt, max_tokens=16)
    got = await run(spec, prompt, max_tokens=16)
    assert got == want
    await plain.close()
    await spec.close()


@pytest.mark.slow
async def test_spec_concurrent_batch_invariance():
    """Multiple concurrent greedy streams under spec decode equal their
    plain counterparts (batched verify, per-row acceptance)."""
    import asyncio

    prompts = [([21, 22, 23, 24] * 5)[:18],
               ([31, 32, 33] * 6)[:17],
               [2, 71, 5, 93, 11, 44, 8, 120]]
    plain = make_engine()
    spec = make_engine(speculative_tokens=3)
    want = await asyncio.gather(*(run(plain, p, 12) for p in prompts))
    got = await asyncio.gather(*(run(spec, p, 12) for p in prompts))
    assert got == want
    await plain.close()
    await spec.close()


async def test_spec_skipped_for_sampled_or_logprobs():
    """Sampled requests and logprobs requests bypass the spec path (it is
    greedy-only and carries no top-k capture)."""
    spec = make_engine(speculative_tokens=4)
    prompt = [11, 12, 13, 14] * 4
    await run(spec, prompt, max_tokens=8, temperature=0.8)
    assert spec.spec_stats.num_drafts == 0
    await run(spec, prompt, max_tokens=8, logprobs=2)
    assert spec.spec_stats.num_drafts == 0
    # and a greedy run immediately after still engages it
    await run(spec, prompt, max_tokens=8)
    assert spec.spec_stats.num_drafts > 0
    await spec.close()


# ---------------------------------------------- layer-skip draft model

def draft_engine(**kw) -> AsyncJaxEngine:
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=4,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64),
                    decode_batch_buckets=(1, 2, 4),
                    speculative_tokens=4,
                    speculative_method="draft_layers",
                    speculative_draft_layers=1)
    defaults.update(kw)
    return AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(**defaults))


async def test_draft_model_greedy_invariance():
    """Layer-skip drafting must emit EXACTLY the plain-greedy tokens,
    whatever the draft quality."""
    prompt = list(range(1, 30))
    plain = make_engine()
    want = await run(plain, prompt)
    await plain.close()

    eng = draft_engine()
    got = await run(eng, prompt)
    assert got == want
    # the draft model drafts every step (unlike prompt-lookup)
    assert eng.spec_stats.num_drafts > 0
    assert eng.spec_stats.num_draft_tokens >= eng.spec_stats.num_drafts
    await eng.close()


@pytest.mark.slow
async def test_draft_model_batched_invariance():
    import asyncio

    prompts = [list(range(1, 25)), list(range(7, 45)), [3, 9, 4, 9, 4, 9, 4]]
    plain = make_engine()
    want = [await run(plain, p) for p in prompts]
    await plain.close()

    eng = draft_engine()
    got = await asyncio.gather(*[run(eng, p) for p in prompts])
    assert list(got) == want
    await eng.close()


async def test_draft_model_acceptance_telemetry():
    """Acceptance accounting: accepted <= drafted, and the worker stats
    surface carries the SpecDecodeStats payload."""
    eng = draft_engine()
    await run(eng, list(range(1, 30)))
    st = eng.spec_stats
    assert 0 <= st.num_accepted_tokens <= st.num_draft_tokens
    assert st.num_spec_tokens >= st.num_drafts  # ≥1 token per dispatch
    assert eng.param_reads > 0
    await eng.close()


async def test_draft_model_full_depth_full_acceptance():
    """draft_layers == num_layers: the draft IS the serving model, so every
    draft must match the verify pass — the sharpest end-to-end check of the
    draft-KV/slot plumbing: any cache corruption from drafting (wrong
    slots, partial-layer residue misread) would break the greedy match."""
    cfg = ModelConfig.tiny()
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=128, max_num_seqs=4,
        max_num_batched_tokens=64, max_model_len=256,
        prefill_buckets=(8, 16, 32, 64), decode_batch_buckets=(1, 2, 4),
        speculative_tokens=4, speculative_method="draft_layers",
        speculative_draft_layers=cfg.num_layers))
    await run(eng, list(range(1, 20)), max_tokens=24)
    st = eng.spec_stats
    # ~100%: the only divergence source is chunked-vs-single-token float
    # reduction order flipping a near-tie argmax, which random tiny
    # weights make vanishingly rare
    assert st.num_accepted_tokens / max(1, st.num_draft_tokens) > 0.9, vars(st)
    await eng.close()


def test_draft_fn_validation():
    cfg = ModelConfig.tiny()
    with pytest.raises(ValueError, match="draft_layers"):
        AsyncJaxEngine(cfg, EngineArgs(
            block_size=4, num_blocks=64, speculative_tokens=4,
            speculative_method="draft_layers",
            speculative_draft_layers=cfg.num_layers + 3))
    with pytest.raises(ValueError, match="speculative_draft_layers"):
        EngineArgs(block_size=4, speculative_tokens=4,
                   speculative_method="draft_layers")
    with pytest.raises(ValueError, match="speculative_method"):
        EngineArgs(block_size=4, speculative_method="magic")


# ------------------------------------------- auto-disable governor (ISSUE 4)

async def test_spec_auto_disables_on_losing_gain_and_reprobes():
    """An early run recorded accept 0.019 / gain 0.729 — a 27% slowdown with
    nothing turning speculation off. The governor must suspend spec decode
    once the rolling measured gain stays < 1 over the window, count it,
    and re-arm after the re-probe interval."""
    eng = make_engine(speculative_tokens=4, spec_gain_window=8,
                      spec_reprobe_steps=100)
    assert eng._spec_active()
    # 8 dispatches that each emitted only the corrected token (accept 0):
    # mean 1.0 tokens/dispatch under a >1 dispatch cost → gain < 1
    for _ in range(8):
        eng._note_spec_result(emitted=2, n_seqs=2)
    assert not eng._spec_active()
    assert eng.spec_disabled_total == 1
    assert eng.spec_measured_gain is not None and eng.spec_measured_gain < 1.0
    # re-probe: once spec_reprobe_steps engine steps pass, spec re-arms
    eng.steps += 100
    assert eng._spec_active()
    # a WINNING window must never trip the governor
    for _ in range(8):
        eng._note_spec_result(emitted=6, n_seqs=2)  # 3 tokens/dispatch
    assert eng._spec_active()
    assert eng.spec_disabled_total == 1
    await eng.close()


async def test_suspended_spec_takes_plain_decode_path():
    """While suspended, decode must not dispatch draft/verify at all (the
    whole point: stop paying for losing speculation)."""
    eng = make_engine(speculative_tokens=4)
    eng._spec_resume_step = 10_000_000  # governor tripped
    prompt = [11, 12, 13, 14] * 4  # repetitive: spec WOULD engage
    toks = await run(eng, prompt, max_tokens=8)
    assert len(toks) == 8
    assert eng.spec_stats.num_drafts == 0
    # and plain greedy output is unchanged
    plain = make_engine()
    assert toks == await run(plain, prompt, max_tokens=8)
    await eng.close()
    await plain.close()
