"""Structured decoding subsystem (docs/structured.md): device-compiled
constraint FSMs in the sampling dispatch, tool-call enforcement, and the
agentic tool-loop workload (ISSUE 13 acceptance).
"""

import asyncio
import json
import re

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.llm.guided import CharDfa, GuidedState, TokenMachine
from dynamo_tpu.protocols import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.structured import (
    COMPILE_STATS,
    FsmCursor,
    StructuredRuntime,
    build_guided_state,
    compile_fsm,
    tool_constraint,
)

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny()
VOCAB = [""] + [chr(32 + i) for i in range(CFG.vocab_size - 1)]
CHAR_VOCAB = [""] + [chr(32 + i) for i in range(95)]


# ------------------------------------------------------------ compiler unit

@pytest.mark.parametrize("pattern", [
    r"[ab]{3}", r"yes|no|maybe", r"a(xy|b)", r"\d+",
    r'"([^"\\]|\\["\\nrt])*"',
])
def test_compiled_tables_mirror_host_oracle(pattern):
    """Every reachable state's mask and transition must equal what the
    host oracle (GuidedState) computes — walked adversarially from both
    ends of the allowed set."""
    tm = TokenMachine(CharDfa(pattern), CHAR_VOCAB)
    V = len(CHAR_VOCAB)
    eos = [2]
    fsm = compile_fsm(tm, eos, V, 2000)
    for pick_last in (False, True):
        gs = GuidedState(tm, eos)
        rt = StructuredRuntime(V, 512)
        seg = rt.acquire(("t", pattern, pick_last), fsm)
        cur = FsmCursor(seg, rt)
        for step in range(48):
            a = sorted(gs.allowed_token_ids(V))
            b = sorted(cur.allowed_token_ids(V))
            assert a == b, (pattern, step, a[:8], b[:8])
            assert (gs.done, gs.exhausted) == (cur.done, cur.exhausted)
            if not a or gs.done or gs.exhausted:
                break
            t = a[-1] if pick_last else a[0]
            gs.advance(t)
            cur.advance(t)


def test_arena_segments_share_and_evict():
    rt = StructuredRuntime(2, 64)
    tm = TokenMachine(CharDfa("ab"), ["a", "b"])
    fsm = compile_fsm(tm, [], 2, 32)
    s1 = rt.acquire("k1", fsm)
    s2 = rt.acquire("k1", fsm)
    assert s1 is s2 and s1.refs == 2  # same constraint shares a segment
    rt.release(s1)
    rt.release(s1)
    # a zero-ref segment is evictable: fill the arena past capacity
    big_tm = TokenMachine(CharDfa("a{40}"), ["a", "b"])
    big = compile_fsm(big_tm, [], 2, 63)
    s3 = rt.acquire("k2", big)
    assert s3 is not None
    assert rt.evictions >= 1 or rt.stats()["segments"] == 2


def test_budget_fallback_to_host_oracle():
    """A constraint whose closure exceeds the arena falls back to the
    host oracle — and still serves correct streams (engine test below)."""
    rt = StructuredRuntime(len(CHAR_VOCAB), 33)  # min arena, 32 usable
    gs = build_guided_state({"regex": "a{64}"}, CHAR_VOCAB, [2], rt)
    assert not getattr(gs, "device", False)
    assert rt.rows_host == 1


def test_compile_cache_counts_hits_and_misses():
    vocab = ["x", "y", "z"]
    rt = StructuredRuntime(len(vocab), 64)
    before = dict(COMPILE_STATS)
    build_guided_state({"regex": "xy+z"}, vocab, [], rt)
    mid = dict(COMPILE_STATS)
    assert mid["miss"] == before["miss"] + 1
    build_guided_state({"regex": "xy+z"}, vocab, [], rt)
    after = dict(COMPILE_STATS)
    assert after["hit"] == mid["hit"] + 1 and after["miss"] == mid["miss"]


def test_free_state_is_identity():
    """Arena row 0 (FREE) must allow every token and self-loop, so an
    unconstrained row through the fused dispatch is untouched."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.sampling import apply_fsm_mask

    rt = StructuredRuntime(37, 64)
    mask_t, next_t = rt.device_tables()
    logits = jnp.asarray(np.linspace(-3, 3, 2 * 37,
                                     dtype=np.float32).reshape(2, 37))
    out = apply_fsm_mask(logits, jnp.zeros((2,), jnp.int32), mask_t)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))
    assert int(next_t[0, 5]) == 0  # self-loop


# ---------------------------------------------------- engine path identity

def _req(guided, seed=None, temp=0.0, mt=24, eos=(2,), min_tokens=None):
    return PreprocessedRequest(
        model="t", token_ids=[1, 2, 3],
        sampling_options=SamplingOptions(temperature=temp, seed=seed,
                                         guided=guided),
        stop_conditions=StopConditions(max_tokens=mt, min_tokens=min_tokens),
        eos_token_ids=list(eos))


async def _collect(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            return toks, out.finish_reason
    return toks, None


def _engine(**kw):
    base = dict(block_size=16, num_blocks=64, max_num_seqs=4,
                max_num_batched_tokens=128, max_model_len=128)
    base.update(kw)
    return AsyncJaxEngine(CFG, EngineArgs(**base), guided_vocab=VOCAB)


GUIDEDS = [
    {"regex": r"[ab]{6}"},
    {"choice": ["apple", "banana"]},
    {"json": {"type": "object", "properties": {"ok": {"type": "boolean"},
                                               "k": {"enum": ["x", "yz"]}}}},
]


async def test_device_fsm_bit_identical_to_host_oracle():
    """The acceptance gate: device-FSM streams must equal the host-DFA
    oracle's bit for bit — greedy AND seeded — while actually riding the
    pipelined decode loop (both ``guided_state is None`` pipeline guards
    are gone)."""
    dev = _engine()
    host = _engine(structured_device=False)
    try:
        assert dev.structured is not None and host.structured is None
        for g in GUIDEDS:
            for seed, temp in [(None, 0.0), (7, 0.9), (123, 0.5)]:
                a = await _collect(dev, _req(g, seed, temp, 32))
                b = await _collect(host, _req(g, seed, temp, 32))
                assert a == b, (g, seed, temp, a, b)
        st = dev.structured.stats()
        assert st["rows_device"] > 0 and st["rows_host"] == 0
        assert dev.pipelined_steps > 0, \
            "constrained rows never rode the pipelined decode loop"
        assert host.pipelined_steps == 0  # oracle rows still force off it
    finally:
        await dev.close()
        await host.close()


async def test_constrained_rows_ride_ragged_mixed_step():
    """A constrained row and a free prefill must co-schedule into ONE
    ragged launch (no bucketed demotion for the constrained row)."""
    dev = _engine(max_num_batched_tokens=64)
    try:
        a, b = await asyncio.gather(
            _collect(dev, _req({"regex": r"[ab]{6}"}, mt=16)),
            _collect(dev, PreprocessedRequest(
                model="t", token_ids=list(range(3, 40)),
                sampling_options=SamplingOptions(temperature=0.0),
                stop_conditions=StopConditions(max_tokens=8,
                                               ignore_eos=True),
                eos_token_ids=[2])))
        txt = "".join(VOCAB[t] for t in a[0] if t != 2)
        assert re.fullmatch(r"[ab]{6}", txt), txt
        assert any(k[0] in ("ragged", "ragged_dec")
                   for k in dev.compiled_signatures)
    finally:
        await dev.close()


async def test_multi_step_burst_constrained_identical():
    host = _engine(structured_device=False)
    multi = _engine(multi_step_decode=4)
    try:
        for g in GUIDEDS:
            assert (await _collect(multi, _req(g))
                    == await _collect(host, _req(g))), g
            assert (await _collect(multi, _req(g, seed=11, temp=0.8))
                    == await _collect(host, _req(g, seed=11, temp=0.8))), g
        kinds = {k[0] for k in multi.compiled_signatures if "multi" in k[0]}
        assert kinds == {"multi_fsm"}, kinds  # burst stayed fused
    finally:
        await host.close()
        await multi.close()


async def test_spec_decode_constrained_identical():
    host = _engine(structured_device=False)
    spec = _engine(speculative_tokens=3)
    try:
        for g in GUIDEDS:
            assert (await _collect(spec, _req(g))
                    == await _collect(host, _req(g))), g
        assert any(k[0] == "verify_fsm" for k in spec.compiled_signatures)
    finally:
        await host.close()
        await spec.close()


async def test_min_tokens_falls_back_to_host_oracle():
    """min_tokens EOS gating is per-step dynamic — those rows must use
    the oracle (documented fallback rule) and still defer EOS."""
    dev = _engine()
    try:
        toks, reason = await _collect(
            dev, _req({"choice": ["hi", "hiyo"]}, mt=16, eos=(5,),
                      min_tokens=4))
        assert "".join(VOCAB[t] for t in toks if t != 5).startswith("hiyo")
        assert dev.structured.stats()["rows_host"] >= 1
    finally:
        await dev.close()


async def test_budget_fallback_engine_stream_still_valid():
    dev = _engine(structured_table_mb=0.0001)  # arena too small to build
    try:
        assert dev.structured is None
        toks, _ = await _collect(dev, _req({"regex": r"[ab]{4}"}))
        txt = "".join(VOCAB[t] for t in toks if t != 2)
        assert re.fullmatch(r"[ab]{4}", txt), txt
    finally:
        await dev.close()


async def test_schema_validity_property():
    """Property over generated schemas: greedy constrained output always
    parses and type-checks against its schema."""
    rng = np.random.default_rng(7)
    schemas = []
    for _ in range(6):
        props = {}
        for pi in range(int(rng.integers(1, 3))):
            kind = int(rng.integers(0, 4))
            name = f"f{pi}"
            if kind == 0:
                props[name] = {"type": "boolean"}
            elif kind == 1:
                # bounded: a bare integer is an unbounded language, and a
                # constraint says which tokens are legal, not when to stop —
                # a model that greedily prefers digits runs into max_tokens
                props[name] = {"type": "integer", "enum": [0, 7, 42, -3]}
            elif kind == 2:
                props[name] = {"enum": ["a", "bc"]}
            else:
                props[name] = {"type": "array", "items": {"type": "boolean"},
                               "minItems": 1, "maxItems": 2}
        schemas.append({"type": "object", "properties": props})
    dev = _engine()
    try:
        for schema in schemas:
            toks, _ = await _collect(dev, _req({"json": schema}, mt=64))
            txt = "".join(VOCAB[t] for t in toks if t != 2)
            obj = json.loads(txt)
            for name, sub in schema["properties"].items():
                v = obj[name]
                if sub.get("type") == "boolean":
                    assert isinstance(v, bool)
                elif sub.get("type") == "integer":
                    assert isinstance(v, int)
                elif "enum" in sub:
                    assert v in sub["enum"]
                else:
                    assert isinstance(v, list) and 1 <= len(v) <= 2
    finally:
        await dev.close()


async def test_flight_records_tag_constrained_rows():
    dev = _engine()
    try:
        await _collect(dev, _req({"regex": r"[ab]{6}"}))
        recs = dev.flight.snapshot()
        assert any(r.get("constrained_rows") for r in recs), \
            "no flight record carried constrained_rows"
    finally:
        await dev.close()


async def test_arena_released_on_finish():
    dev = _engine()
    try:
        await _collect(dev, _req({"regex": r"[ab]{4}"}))
        segs = list(dev.structured._segments.values())
        assert segs and all(s.refs == 0 for s in segs)
    finally:
        await dev.close()


def test_unsatisfiable_constraint_is_typed_invalid_request():
    """The vocabulary-refusal is DETERMINISTIC fleet-wide, so it must be
    a typed terminal error (never migrated, frontend 400) that survives
    the wire — review-round fix."""
    from dynamo_tpu.runtime.context import (
        InvalidRequestError, stream_error_from_wire,
    )

    rt = StructuredRuntime(3, 64)
    with pytest.raises(InvalidRequestError):
        build_guided_state({"regex": r"\d+"}, ["a", "b"], [], rt)
    e = stream_error_from_wire("x", "invalid_request", True)
    assert isinstance(e, InvalidRequestError) and not e.retryable


# -------------------------------------------------- tool_choice enforcement

TOOLS = [
    {"type": "function", "function": {
        "name": "get_weather",
        "parameters": {"type": "object",
                       "properties": {"city": {"enum": ["paris", "nyc"]}}}}},
    {"type": "function", "function": {
        "name": "get_time", "parameters": {
            "type": "object", "properties": {"tz": {"type": "integer"}}}}},
]


def _chat(**kw):
    body = {"model": "m", "messages": [{"role": "user", "content": "hi"}]}
    body.update(kw)
    from dynamo_tpu.protocols.openai import parse_chat_request
    return parse_chat_request(body)


def test_tool_choice_parse_matrix():
    from dynamo_tpu.protocols.openai import RequestError

    assert _chat(tools=TOOLS, tool_choice="required").tool_choice \
        == "required"
    assert _chat(tools=TOOLS).tool_choice is None
    named = {"type": "function", "function": {"name": "get_time"}}
    assert _chat(tools=TOOLS, tool_choice=named).tool_choice == named
    for bad, msg in [
            (dict(tools=TOOLS, tool_choice="banana"), "must be"),
            (dict(tool_choice="required"), "requires 'tools'"),
            (dict(tools=TOOLS, tool_choice={"type": "function",
                                            "function": {"name": "nope"}}),
             "unknown tool"),
            (dict(tools=TOOLS, tool_choice="required",
                  guided_regex="a+"), "cannot be combined"),
            (dict(tools=[{"function": {}}], tool_choice="auto"),
             "each tool"),
    ]:
        with pytest.raises(RequestError, match=msg):
            _chat(**bad)


def test_tool_constraint_grammar_per_parser():
    pat = tool_constraint(TOOLS, "required", None)
    d = CharDfa(pat)
    assert d.fullmatch('{"name":"get_weather","arguments":{"city":"nyc"}}')
    assert d.fullmatch('{"name":"get_time","arguments":{"tz":-5}}')
    assert not d.fullmatch('{"name":"evil","arguments":{}}')
    # named tool restricts the union
    named = {"type": "function", "function": {"name": "get_time"}}
    dn = CharDfa(tool_constraint(TOOLS, named, None))
    assert dn.fullmatch('{"name":"get_time","arguments":{"tz":0}}')
    assert not dn.fullmatch(
        '{"name":"get_weather","arguments":{"city":"nyc"}}')
    # parser wrappers round-trip through the real parsers
    from dynamo_tpu.parsers import parse_tool_calls
    h = '<tool_call>{"name":"get_time","arguments":{"tz":1}}</tool_call>'
    assert CharDfa(tool_constraint(TOOLS, "required", "hermes")).fullmatch(h)
    _, calls = parse_tool_calls("hermes", h)
    assert calls and calls[0].name == "get_time"
    m = '[TOOL_CALLS][{"name":"get_time","arguments":{"tz":1}}]'
    assert CharDfa(tool_constraint(TOOLS, "required",
                                   "mistral")).fullmatch(m)
    _, calls = parse_tool_calls("mistral", m)
    assert calls and calls[0].name == "get_time"
    with pytest.raises(ValueError, match="not supported"):
        tool_constraint(TOOLS, "required", "harmony")


def test_pipeline_enforces_tool_choice():
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import OpenAIPreprocessor
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer

    tk = make_test_tokenizer()
    pipe = OpenAIPreprocessor(
        ModelDeploymentCard(display_name="m",
                            eos_token_ids=[tk.eos_token_id]), tk, None)
    # "none" strips tools before the template renders
    r, enforced = pipe._apply_tool_choice(
        _chat(tools=TOOLS, tool_choice="none"))
    assert r.tools is None and not enforced and r.sampling.guided is None
    # "required" attaches the constraint; the original request is untouched
    orig = _chat(tools=TOOLS, tool_choice="required")
    r, enforced = pipe._apply_tool_choice(orig)
    assert enforced and r.sampling.guided and "regex" in r.sampling.guided
    assert orig.sampling.guided is None
    # auto passes through unconstrained
    r, enforced = pipe._apply_tool_choice(_chat(tools=TOOLS))
    assert not enforced and r.sampling.guided is None


async def test_tool_choice_required_end_to_end():
    """Frontend-shaped flow: required → constrained generation → the tool
    parser surfaces the call with finish_reason tool_calls."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import OpenAIPreprocessor
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.runtime.context import Context

    tk = make_test_tokenizer()
    eng = _engine()

    async def downstream(pre, ctx):
        async for out in eng.generate(pre, ctx):
            # pipeline expects detokenized text on each output; decode via
            # the engine's guided vocab (test tokenizer has no JSON chars)
            out.text = "".join(VOCAB[t] for t in out.token_ids
                               if t not in pre.eos_token_ids)
            yield out

    pipe = OpenAIPreprocessor(
        ModelDeploymentCard(display_name="m", eos_token_ids=[2]), tk,
        downstream)
    parsed = _chat(tools=TOOLS, tool_choice="required", max_tokens=64)
    chunks = []
    try:
        async for wire in pipe.generate(parsed, Context()):
            chunks.append(wire)
    finally:
        await eng.close()
    finals = [c["data"] for c in chunks if c.get("data")]
    tool_calls = [tc for ch in finals
                  for choice in ch.get("choices", [])
                  for tc in (choice.get("delta") or {}).get("tool_calls",
                                                            [])]
    finish = [choice.get("finish_reason")
              for ch in finals for choice in ch.get("choices", [])
              if choice.get("finish_reason")]
    assert tool_calls, finals
    fn = tool_calls[0]["function"]
    assert fn["name"] in ("get_weather", "get_time")
    json.loads(fn["arguments"])
    assert finish == ["tool_calls"]


def test_qos_tool_class_mapping():
    from dynamo_tpu.qos import ConfigError, QosConfig

    cfg = QosConfig.load(env={"DYN_QOS_TOOL_CLASS": "interactive"})
    assert cfg.tool_class == "interactive"
    with pytest.raises(ConfigError, match="unknown class"):
        QosConfig.load(env={"DYN_QOS_TOOL_CLASS": "vip"})

    # frontend resolution: tools adopt the class, explicit header wins
    from dynamo_tpu.frontend.http import HttpService

    class FakeReq:
        def __init__(self, headers):
            self.headers = headers

    svc = HttpService.__new__(HttpService)
    svc.qos = cfg
    svc._adhoc_tenants = set()
    svc._adhoc_overflow_warned = False
    t, c = HttpService._resolve_qos(svc, FakeReq({}), has_tools=True)
    assert c == "interactive"
    t, c = HttpService._resolve_qos(
        svc, FakeReq({"x-dynamo-priority": "batch"}), has_tools=True)
    assert c == "batch"
    t, c = HttpService._resolve_qos(svc, FakeReq({}), has_tools=False)
    assert c == "standard"


# --------------------------------------------------------------- mocker

async def test_mocker_guided_parity():
    from dynamo_tpu.mocker.engine import (
        MockEngine, MockEngineArgs, mock_guided_vocab,
    )
    from dynamo_tpu.runtime.context import Context

    eng = MockEngine(MockEngineArgs(speedup_ratio=100.0))
    await eng.start()
    try:
        req = PreprocessedRequest(
            model="m", token_ids=[1, 2, 3],
            sampling_options=SamplingOptions(
                temperature=0.0,
                guided={"json": {"type": "object", "properties": {
                    "ok": {"type": "boolean"}}}}),
            stop_conditions=StopConditions(max_tokens=64),
            eos_token_ids=[2])
        toks, reasons = [], []
        async for out in eng.generate(req, Context()):
            toks.extend(out.get("token_ids") or [])
            if out.get("finish_reason"):
                reasons.append(out["finish_reason"])
                break
        v = mock_guided_vocab()
        obj = json.loads("".join(v[t] for t in toks if t != 2))
        assert isinstance(obj.get("ok"), bool)
        # two identical requests emit identical canned streams
        toks2 = []
        async for out in eng.generate(req, Context()):
            toks2.extend(out.get("token_ids") or [])
            if out.get("finish_reason"):
                break
        assert toks2 == toks
        # timeline records carry the per-row constraint shape
        assert any(r.get("constrained_rows")
                   for r in eng.flight.snapshot())
    finally:
        await eng.stop()


# ------------------------------------------------ the agentic tool loop

LOOP_TOOLS = [
    {"type": "function", "function": {
        "name": "get", "parameters": {
            "type": "object",
            "properties": {"k": {"enum": ["a", "b"]}}}}},
    {"type": "function", "function": {
        "name": "put", "parameters": {
            "type": "object",
            # n is bounded: a bare integer is an unbounded language, and
            # the random-weight model greedily emits digits up to OSL
            "properties": {"k": {"enum": ["a", "b"]},
                           "n": {"type": "integer",
                                 "enum": [0, 1, 12, 250]}}}}},
]
LOOP_OSL = 48  # the char-level tool-call JSON needs ~40 tokens to close
LOOP_EOS = 2


def _loop_req(tokens, pin=None):
    return PreprocessedRequest(
        model="m", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=LOOP_OSL),
        sampling_options=SamplingOptions(
            temperature=0.0,
            guided={"regex": tool_constraint(LOOP_TOOLS, "required", None)}),
        eos_token_ids=[LOOP_EOS], backend_instance_id=pin)


def _loop_call_valid(toks) -> bool:
    try:
        obj = json.loads("".join(VOCAB[t] for t in toks if t != LOOP_EOS))
    except ValueError:
        return False
    return (isinstance(obj, dict) and obj.get("name") in {"get", "put"}
            and isinstance(obj.get("arguments"), dict))


async def _tools_peer_leg() -> dict:
    """2-worker tool loop: a session's first turn lands on worker A, its
    second is steered to worker B, whose admission peer-pulls the session's
    own prefix over the onboarding wire (constrained throughout)."""
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler, KvPullHandler
    from dynamo_tpu.disagg.transfer import OnboardConfig, RestoreConfig
    from dynamo_tpu.router.kv_router import KvPushRouter, KvRouter
    from dynamo_tpu.router.protocols import KvRouterConfig
    from dynamo_tpu.router.publisher import KvEventPublisher
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.context import Context

    bs = 16
    isl = 512  # enough prefix blocks to clear onboard_min_blocks
    rng = np.random.default_rng(67)
    prefix = rng.integers(3, CFG.vocab_size, isl).tolist()
    rcfg = RuntimeConfig(lease_ttl=8.0)
    rt = await DistributedRuntime.create(config=rcfg)
    workers = []
    router = client = None

    async def make_worker():
        wrt = await DistributedRuntime.create(plane=rt.plane,
                                              owns_plane=False, config=rcfg)
        lease = await wrt.primary_lease()
        eng = await asyncio.to_thread(
            AsyncJaxEngine, CFG, EngineArgs(
                block_size=bs, num_blocks=4 * (isl // bs) + 64,
                max_num_seqs=4, max_num_batched_tokens=1024,
                max_model_len=isl + 4 * (LOOP_OSL + 16) + bs,
                enable_prefix_caching=True), guided_vocab=VOCAB)
        pub = KvEventPublisher(wrt.plane, worker_id=lease, kv_block_size=bs)
        await pub.start_resync_responder()
        eng.event_cb = pub.publish_sync
        comp = wrt.namespace("dynamo").component("backend")
        pull_client = await comp.endpoint("kv_pull").client().start()
        handler = DecodeWorkerHandler(
            eng, pull_clients=[pull_client], metrics=wrt.metrics,
            restore_config=RestoreConfig(enabled=False),
            onboard_config=OnboardConfig(enabled=True))
        handler.instance_id = lease
        h_gen = await comp.endpoint("generate").serve_endpoint(
            handler.generate, lease_id=lease)
        h_pull = await comp.endpoint("kv_pull").serve_endpoint(
            KvPullHandler(eng).generate, lease_id=lease)
        w = type("W", (), {})()
        w.rt, w.engine, w.lease, w.handler = wrt, eng, lease, handler
        w.pub, w.pull_client, w.handles = pub, pull_client, [h_gen, h_pull]
        workers.append(w)
        return w

    try:
        a = await make_worker()
        b = await make_worker()
        client = await (rt.namespace("dynamo").component("backend")
                        .endpoint("generate").client().start())
        router = await KvRouter(rt.plane, bs, KvRouterConfig()).start()
        push = KvPushRouter(client, router)

        async def turn(tokens, pin=None):
            toks = []
            async for out in push.generate(_loop_req(tokens, pin), Context()):
                if isinstance(out, dict) and out.get("token_ids"):
                    toks.extend(out["token_ids"])
            return toks

        # turn 1 computes the session prefix on A
        state = prefix + [5]
        t1 = await turn(state, pin=a.lease)
        state = state + t1 + rng.integers(3, CFG.vocab_size, 32).tolist()
        # radix must learn A's prefix before steering away
        for _ in range(400):
            if router.restore_sources(state).get(a.lease, 0) \
                    >= isl // bs - 1:
                break
            await asyncio.sleep(0.02)
        client.set_busy_instances([a.lease])  # turn 2 lands on B
        t2 = await turn(state)
        pulled = b.handler._onboard_blocks._values.get(
            (("source", "peer"),), 0)
        return {"pulled_blocks": int(pulled),
                "complete": bool(t1 and t2 and _loop_call_valid(t1)
                                 and _loop_call_valid(t2))}
    finally:
        for w in workers:
            for h in w.handles:
                await h.stop(graceful=False)
            await w.pull_client.stop()
            await w.pub.stop()
            await w.engine.close()
            await w.rt.shutdown()
        if router is not None:
            await router.stop()
        if client is not None:
            await client.stop()
        await rt.shutdown()


async def test_tools_bench_smoke():
    """The agentic tool loop as a workload: multi-turn tool-call sessions
    under ``tool_choice: "required"`` on the device-FSM path, each turn's
    prompt the previous turn's prompt + the model's tool call + a synthetic
    tool result. Every constrained turn is schema-valid, turn 2 re-hits its
    own prefix in the radix cache, no row falls back to the host oracle,
    and on a 2-worker fleet a session steered to the other worker
    peer-pulls its own prefix and stays schema-valid."""
    turns, bs = 2, 16
    rng = np.random.default_rng(61)
    base_prompt = rng.integers(3, CFG.vocab_size, 96).tolist()
    result_filler = [rng.integers(3, CFG.vocab_size, 48).tolist()
                     for _ in range(turns)]
    max_len = len(base_prompt) + turns * (LOOP_OSL + 48) + 64
    eng = AsyncJaxEngine(CFG, EngineArgs(
        block_size=bs, num_blocks=8 * (max_len // bs) + 16, max_num_seqs=2,
        max_num_batched_tokens=512, max_model_len=max_len,
        enable_prefix_caching=True), guided_vocab=VOCAB)
    assert eng.structured is not None, "device FSM arena failed to build"

    async def session(salt):
        """One session running alone, so the scheduler's (global) hit
        counter's per-turn deltas are that session's own prefix re-hits.
        Returns (schema-valid turns, turn-2+ prefix-hit tokens)."""
        state = base_prompt + [9 + salt]
        valid = hits = 0
        for t in range(turns):
            h0 = eng.scheduler.prefix_hit_tokens
            toks, _ = await _collect(eng, _loop_req(state))
            valid += _loop_call_valid(toks)
            if t > 0:
                hits += eng.scheduler.prefix_hit_tokens - h0
            state = state + toks + result_filler[t]
        return valid, hits

    try:
        for salt in range(2):
            valid, hits = await session(salt)
            assert valid == turns
            assert hits > 0
        st = eng.structured.stats()
    finally:
        await eng.close()
    assert st["rows_host"] == 0, st
    assert st["rows_device"] > 0, st

    peer = await _tools_peer_leg()
    assert peer["pulled_blocks"] > 0 and peer["complete"], peer
