"""Session-native serving (docs/sessions.md, PR 20).

Coverage, per the issue's falsifiable list:
  * delta-turn streams bit-identical (greedy/seeded) to full-prompt resends
  * affinity-vs-load tradeoff: a saturated affinity worker sheds the session
  * park → return restore through G4 (KVBM tier ladder round trip)
  * abandoned-session reaping (TTL) + registry cap guard
  * typed 404 on unknown/superseded/disabled previous_response_id
  * mocker parity: fleet drives carry session traffic end-to-end
"""

import asyncio
import json

import aiohttp
import numpy as np
import pytest

from dynamo_tpu.frontend.http import HttpService
from dynamo_tpu.kvbm import KvbmManager
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
from dynamo_tpu.llm.tokenizer import make_test_tokenizer
from dynamo_tpu.mocker.engine import MockEngineArgs
from dynamo_tpu.mocker.main import run_mocker
from dynamo_tpu.router.indexer import OverlapScores
from dynamo_tpu.router.protocols import KvRouterConfig
from dynamo_tpu.router.scheduler import KvScheduler
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.sessions import (
    SessionConfig, SessionEntry, SessionKvHandler, SessionRegistry,
    UnknownResponseError, session_prefix_hashes,
)

pytestmark = pytest.mark.anyio

MODEL = "mock-model"
TK = make_test_tokenizer()


# -- registry lifecycle (unit, injected clock) -------------------------------


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def make_registry(**cfg):
    clock = Clock()
    defaults = dict(ttl_s=60.0, park_after_s=10.0, max_sessions=8)
    defaults.update(cfg)
    reg = SessionRegistry(SessionConfig(**defaults), clock=clock)
    return reg, clock


def test_registry_turn_and_response_chain():
    reg, clock = make_registry()
    e = reg.get_or_create("s1", MODEL)
    assert reg.begin_turn(e, kind="first") is False
    reg.note_routed(e, worker_id=0xAB, token_ids=[1, 2, 3])
    reg.complete_turn(e, "resp-1", [{"role": "user", "content": "hi"}],
                      "hello", delta_chars_saved=0)
    assert reg.resolve_response("resp-1") is e
    assert e.messages[-1] == {"role": "assistant", "content": "hello"}
    # a later turn supersedes the id: only the latest resolves
    reg.begin_turn(e, kind="delta")
    reg.complete_turn(e, "resp-2", list(e.messages), "again")
    assert reg.resolve_response("resp-2") is e
    with pytest.raises(UnknownResponseError):
        reg.resolve_response("resp-1")
    with pytest.raises(UnknownResponseError):
        reg.resolve_response("resp-never-existed")


def test_registry_ttl_reaps_abandoned_sessions():
    reg, clock = make_registry(ttl_s=60.0)
    e = reg.get_or_create("abandoned", MODEL)
    reg.begin_turn(e)
    reg.complete_turn(e, "resp-a", [{"role": "user", "content": "x"}], "y")
    clock.t += 59
    assert reg.reap() == []          # not yet
    clock.t += 2
    dead = reg.reap()
    assert [d.sid for d in dead] == ["abandoned"]
    assert len(reg) == 0
    with pytest.raises(UnknownResponseError):
        reg.resolve_response("resp-a")  # the chain died with the session


def test_registry_ttl_spares_inflight_turns():
    reg, clock = make_registry(ttl_s=60.0)
    e = reg.get_or_create("slow", MODEL)
    reg.begin_turn(e)                 # turn in flight, never completed
    clock.t += 120
    assert reg.reap() == []           # active turns are never reaped
    reg.abort_turn(e)                 # abort refreshes last_seen
    clock.t += 61
    assert [d.sid for d in reg.reap()] == ["slow"]


def test_registry_cap_guard_serves_statelessly():
    reg, clock = make_registry(max_sessions=2)
    assert reg.get_or_create("a", MODEL) is not None
    assert reg.get_or_create("b", MODEL) is not None
    assert reg.get_or_create("c", MODEL) is None     # at the cap: stateless
    assert reg.get_or_create("a", MODEL).sid == "a"  # existing still resolves
    # reaping frees a slot
    clock.t += 100
    reg.reap()
    assert reg.get_or_create("c", MODEL) is not None


def test_registry_park_candidates_and_affinity_ledger():
    reg, clock = make_registry(park_after_s=10.0)
    e = reg.get_or_create("s", MODEL)
    reg.begin_turn(e)
    clock.t += 50
    assert reg.park_candidates() == []   # active turn: never parked
    reg.note_routed(e, worker_id=7, token_ids=list(range(12)))
    reg.complete_turn(e, "resp-1", [], "ok")
    clock.t += 11
    assert reg.park_candidates() == [e]
    reg.note_parked(e, 3)
    assert e.parked and e.parked_blocks == 3
    assert reg.park_candidates() == []   # parked once, not re-fired
    # the returning turn reports it was parked exactly once
    assert reg.begin_turn(e, kind="delta") is True
    assert reg.begin_turn(e, kind="delta") is False
    # affinity ledger follows the router hook
    assert e.worker_id == 7
    reg.note_routed(e, worker_id=9)      # shed to another worker
    assert e.worker_id == 9


# -- router affinity term (unit) ---------------------------------------------


def _sched(**cfg):
    import random
    defaults = dict(router_temperature=0.0)
    defaults.update(cfg)
    return KvScheduler(block_size=4, config=KvRouterConfig(**defaults),
                       rng=random.Random(0))


def test_scheduler_affinity_breaks_tie_toward_session_worker():
    """Equal load, zero overlap: the affinity term is the deciding vote."""
    workers = [1, 2]
    for _ in range(20):
        s = _sched(session_affinity_weight=1.0)
        d = s.schedule("r", isl_tokens=64, seq_hashes=None,
                       overlaps=OverlapScores(), worker_ids=workers,
                       affinity_worker=2)
        assert d.worker_id == 2


def test_scheduler_affinity_sheds_under_load():
    """A saturated affinity worker loses to an idle one: the discount is
    bounded by the request's own prefill size, so the decode-load term can
    outvote it — sessions are soft state, not pinning."""
    s = _sched(session_affinity_weight=1.0)
    # pile active decode blocks onto worker 2 (the affinity worker)
    for i in range(32):
        blocks = list(range(i * 64, i * 64 + 64))
        s.slots.add_request(f"busy{i}", 2, blocks, 256, 0)
    d = s.schedule("r", isl_tokens=64, seq_hashes=None,
                   overlaps=OverlapScores(), worker_ids=[1, 2],
                   affinity_worker=2)
    assert d.worker_id == 1


def test_scheduler_affinity_weight_zero_disables_term():
    import random
    picks = set()
    for seed in range(10):
        s = KvScheduler(block_size=4,
                        config=KvRouterConfig(router_temperature=0.0,
                                              session_affinity_weight=0.0),
                        rng=random.Random(seed))
        d = s.schedule("r", isl_tokens=64, seq_hashes=None,
                       overlaps=OverlapScores(), worker_ids=[1, 2],
                       affinity_worker=2)
        picks.add(d.worker_id)
    assert picks == {1, 2}  # pure tie-break: both workers show up


# -- park → restore through G4 (KVBM tier ladder) ----------------------------


class _FakeG4Client:
    def __init__(self):
        self.store: dict = {}

    def put(self, h, data):
        self.store[h] = data

    def get(self, h):
        return self.store.get(h)

    def delete(self, h):
        self.store.pop(h, None)


class _FakeEngine:
    """Just enough engine surface for SessionKvHandler: .kvbm + .args."""

    def __init__(self, kvbm, block_size=4):
        self.kvbm = kvbm
        from types import SimpleNamespace
        self.args = SimpleNamespace(block_size=block_size)


def _page(i, nbytes=256):
    return np.full((nbytes // 4,), i, np.float32)


async def _session_op(handler, op, token_ids):
    out = []
    async for frame in handler.generate({"op": op, "token_ids": token_ids}):
        out.append(frame)
    assert len(out) == 1
    return out[0]


async def test_park_restore_through_g4(tmp_path):
    token_ids = list(range(17))         # 4 complete blocks + ragged tail
    hashes = session_prefix_hashes(token_ids, 4)
    assert len(hashes) == 4

    g4 = _FakeG4Client()
    m = KvbmManager(host_bytes=8 * 512, disk_dir=str(tmp_path / "a"),
                    disk_bytes=16 * 512)
    m.attach_remote(g4, capacity_bytes=1 << 20)
    for h in hashes:
        m.put(h, _page(h & 0xFF), _page(h & 0xFF))

    handler = SessionKvHandler(_FakeEngine(m))
    parked = await _session_op(handler, "park", token_ids)
    assert parked["ok"] and parked["op"] == "park"
    assert parked["blocks"] == 4 and parked["published"] == 4
    assert len(g4.store) == 4           # the chain actually landed in G4
    # re-park is idempotent: already remote, nothing re-published
    parked2 = await _session_op(handler, "park", token_ids)
    assert parked2["blocks"] == 4 and parked2["published"] == 0

    # the session returns at a cold worker: fresh local tiers, same G4
    m2 = KvbmManager(host_bytes=8 * 512, disk_dir=str(tmp_path / "b"),
                     disk_bytes=16 * 512)
    m2.attach_remote(g4, capacity_bytes=1 << 20)
    assert m2.match_prefix(hashes) == 0
    restored = await _session_op(handler.__class__(_FakeEngine(m2)),
                                 "restore", token_ids)
    assert restored["ok"] and restored["blocks"] == 4
    assert m2.match_prefix(hashes) == 4  # host-resident again
    k, _ = m2.get(hashes[0])
    np.testing.assert_array_equal(k, _page(hashes[0] & 0xFF))


async def test_restored_session_computes_fewer_prompt_tokens():
    """What parking buys, on a real engine: a session's first turn is
    parked to G4, every local tier is flushed (the churn between turns),
    and the returning turn is served twice — after a ``restore`` op and,
    flushed again, without one. Both read the same greedy tokens; the
    restored arm hits its own prefix and computes strictly fewer prompt
    tokens."""
    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (PreprocessedRequest, SamplingOptions,
                                      StopConditions)

    cfg, bs = ModelConfig.tiny(), 4
    blk = 2 * cfg.num_layers * bs * cfg.num_kv_heads * cfg.head_dim * 4
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=bs, num_blocks=128, max_num_seqs=2,
        max_num_batched_tokens=64, max_model_len=128,
        enable_prefix_caching=True, kvbm_host_bytes=64 * blk))
    g4 = _FakeG4Client()
    eng.kvbm.attach_remote(g4, capacity_bytes=1 << 30)
    handler = SessionKvHandler(eng)
    turn1 = [(7 * i) % 200 + 1 for i in range(12 * bs)]
    turn2 = turn1 + [9, 8, 7, 6, 5]

    async def serve(tokens):
        """(greedy tokens, prompt tokens computed)"""
        sched = eng.scheduler
        q0, h0 = sched.prefix_query_tokens, sched.prefix_hit_tokens
        toks = []
        async for out in eng.generate(PreprocessedRequest(
                model="m", token_ids=list(tokens),
                stop_conditions=StopConditions(max_tokens=6,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))):
            toks.extend(out.token_ids)
        return toks, ((sched.prefix_query_tokens - q0)
                      - (sched.prefix_hit_tokens - h0))

    async def flush():
        """What churn does: the device pool and the host tier are evicted;
        the object store keeps what was parked."""
        eng.pool.clear()
        await asyncio.to_thread(eng.kvbm.make_host_room, 0)

    try:
        await serve(turn1)
        for _ in range(500):  # the turn's blocks reach the host tier
            if eng.kvbm.stats()["host_blocks"] >= 12:
                break
            await asyncio.sleep(0.02)
        parked = await _session_op(handler, "park", turn1)
        assert parked["blocks"] >= 11 and len(g4.store) >= 11
        await flush()
        restored = await _session_op(handler, "restore", turn1)
        assert restored["blocks"] >= 11
        native, native_computed = await serve(turn2)
        await flush()
        control, control_computed = await serve(turn2)
    finally:
        await eng.close()
    assert native == control
    assert control_computed == len(turn2)
    assert native_computed < control_computed - 10 * bs


async def test_park_stops_at_first_gap(tmp_path):
    """A hole in the local chain truncates the park: G4 onboarding attaches
    contiguous prefixes only, so blocks behind the gap would be stranded."""
    token_ids = list(range(16))
    hashes = session_prefix_hashes(token_ids, 4)
    g4 = _FakeG4Client()
    m = KvbmManager(host_bytes=8 * 512, disk_dir=str(tmp_path),
                    disk_bytes=16 * 512)
    m.attach_remote(g4, capacity_bytes=1 << 20)
    for h in (hashes[0], hashes[2], hashes[3]):   # hashes[1] missing
        m.put(h, _page(1), _page(1))
    parked = await _session_op(SessionKvHandler(_FakeEngine(m)),
                               "park", token_ids)
    assert parked["blocks"] == 1 and parked["published"] == 1
    assert set(g4.store) == {hashes[0]}


async def test_session_kv_handler_stub_and_errors():
    h = SessionKvHandler(None)           # mocker arm: no engine at all
    out = await _session_op(h, "park", list(range(8)))
    assert out == {"ok": True, "op": "park", "blocks": 0, "stub": True}
    out = await _session_op(h, "restore", list(range(8)))
    assert out["stub"] and out["blocks"] == 0
    frames = []
    async for f in h.generate({"op": "evict"}):
        frames.append(f)
    assert "error" in frames[0]


# -- e2e: frontend + mocker fleet (mocker parity) ----------------------------


def mock_args(**kw):
    kw.setdefault("vocab_size", TK.vocab_size)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_gpu_blocks", 256)
    kw.setdefault("speedup_ratio", 20.0)
    return MockEngineArgs(**kw)


@pytest.fixture
async def stack():
    rt = await DistributedRuntime.create()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
    service = HttpService(manager, port=0)
    await service.start()
    engines = []

    async def add_mocker(**kw):
        lease = await rt.plane.lease_create(30)
        (engine,), (handle,) = await run_mocker(
            rt, MODEL, mock_args(**kw), lease_id=lease)
        engines.append((engine, handle))
        return engine, handle

    try:
        yield rt, service, add_mocker, manager
    finally:
        await service.stop()
        await watcher.stop()
        for engine, handle in engines:
            await handle.stop(graceful=False)
            await engine.stop()
        await rt.shutdown()


async def wait_for_model(manager: ModelManager, timeout=5.0):
    for _ in range(int(timeout / 0.05)):
        if manager.get(MODEL):
            return
        await asyncio.sleep(0.05)
    raise TimeoutError("model never appeared")


async def _responses_text(http, base, body, headers=None):
    async with http.post(f"{base}/v1/responses", json=body,
                         headers=headers or {}) as r:
        assert r.status == 200, await r.text()
        out = await r.json()
        return out["id"], out["output"][0]["content"][0]["text"]


async def _responses_sse_text(http, base, body, headers=None):
    """Drive the streaming arm; returns (response_id, concatenated deltas)."""
    parts, rid = [], None
    async with http.post(f"{base}/v1/responses", json=body,
                         headers=headers or {}) as r:
        assert r.status == 200, await r.text()
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            ev = json.loads(payload)
            if ev.get("type") == "response.output_text.delta":
                parts.append(ev.get("delta") or "")
            elif ev.get("type") in ("response.completed",
                                    "response.incomplete"):
                rid = ev["response"]["id"]
    return rid, "".join(parts)


async def test_unknown_previous_response_id_is_typed_404(stack):
    rt, service, add_mocker, manager = stack
    await add_mocker()
    await wait_for_model(manager)
    base = f"http://127.0.0.1:{service.port}"
    async with aiohttp.ClientSession() as http:
        body = {"model": MODEL, "input": "continue please",
                "previous_response_id": "resp-does-not-exist",
                "max_output_tokens": 4}
        async with http.post(f"{base}/v1/responses", json=body) as r:
            assert r.status == 404
            err = (await r.json())["error"]
            assert err["type"] == "previous_response_not_found"
        # malformed id shape is a 400, not a silent fallback either
        body["previous_response_id"] = ""
        async with http.post(f"{base}/v1/responses", json=body) as r:
            assert r.status == 400


async def test_delta_turns_bit_identical_to_full_resend(stack):
    """The tentpole correctness gate: a session's delta turn (server-side
    history + new input only) must produce the byte-identical stream a
    sessionless client resending the whole conversation gets. Greedy
    sampling; the mocker derives its stream deterministically from the
    reconstructed prompt token ids, so any prompt divergence shows."""
    rt, service, add_mocker, manager = stack
    await add_mocker()
    await add_mocker()
    await wait_for_model(manager)
    base = f"http://127.0.0.1:{service.port}"
    sampling = {"temperature": 0.0, "max_output_tokens": 8}

    user_turns = ["the quick brown fox jumps over the lazy dog",
                  "now tell me about rivers and stones",
                  "and finally sum it all up briefly"]

    async with aiohttp.ClientSession() as http:
        # session arm: turn 1 full, turns 2..n ship only the delta
        prev, transcript, session_texts = None, [], []
        for turn in user_turns:
            item = {"role": "user", "content": turn}
            body = {"model": MODEL, "input": [item], **sampling}
            if prev:
                body["previous_response_id"] = prev
            prev, text = await _responses_text(http, base, body)
            transcript += [item, {"role": "assistant", "content": text}]
            session_texts.append(text)

        # sessionless arm: full transcript every turn (store=false keeps
        # this arm out of the registry entirely)
        replay, sessionless_texts = [], []
        for turn in user_turns:
            replay.append({"role": "user", "content": turn})
            body = {"model": MODEL, "input": list(replay), "store": False,
                    **sampling}
            _, text = await _responses_text(http, base, body)
            replay.append({"role": "assistant", "content": text})
            sessionless_texts.append(text)

        assert session_texts == sessionless_texts  # bit-identical turns

        # and the streaming path agrees with the aggregate path
        body = {"model": MODEL, "input": list(replay) + [
            {"role": "user", "content": "one more thing"}],
            "store": False, "stream": True, **sampling}
        _, sse_text = await _responses_sse_text(http, base, body)
        body.pop("stream")
        _, agg_text = await _responses_text(http, base, body)
        assert sse_text == agg_text


async def test_session_registry_view_and_metrics(stack):
    """Mocker parity: session traffic over a fleet shows up in
    /v1/sessions and dynamo_session_* metrics, and the affinity worker is
    learned from the router's on_routed hook."""
    rt, service, add_mocker, manager = stack
    await add_mocker()
    await add_mocker()
    await wait_for_model(manager)
    base = f"http://127.0.0.1:{service.port}"
    sampling = {"temperature": 0.0, "max_output_tokens": 6}

    async with aiohttp.ClientSession() as http:
        prev = None
        workers = set()
        for i in range(3):
            body = {"model": MODEL,
                    "input": [{"role": "user", "content": f"turn {i}: "
                               "the quick brown fox jumps over the dog"}],
                    **sampling}
            if prev:
                body["previous_response_id"] = prev
            async with http.post(f"{base}/v1/responses", json=body) as r:
                assert r.status == 200, await r.text()
                prev = (await r.json())["id"]
            async with http.get(f"{base}/v1/sessions") as r:
                snap = await r.json()
                assert snap["enabled"] and snap["count"] >= 1
                sess = snap["sessions"][0]
                if sess["worker"]:
                    workers.add(sess["worker"])
        assert snap["sessions"][0]["turns"] == 3
        assert workers                      # on_routed stamped a worker
        # a returning session keeps its affinity worker on a calm fleet
        assert len(workers) == 1

        # chat route rides the same registry via the soft header
        chat = {"model": MODEL, "max_tokens": 4,
                "messages": [{"role": "user", "content": "hello session"}]}
        async with http.post(f"{base}/v1/chat/completions", json=chat,
                             headers={"x-dynamo-session": "chat-s1"}) as r:
            assert r.status == 200, await r.text()
        async with http.get(f"{base}/v1/sessions") as r:
            snap = await r.json()
            assert any(s["id"] == "chat-s1" for s in snap["sessions"])

        async with http.get(f"{base}/metrics") as r:
            text = await r.text()
            assert "dynamo_session_active" in text
            assert 'dynamo_session_turns_total{kind="delta"}' in text
            assert 'kind="chat"' in text
            assert "dynamo_session_affinity_total" in text
            assert "dynamo_session_parked_blocks_total" in text


async def test_reaper_parks_idle_session_via_worker_endpoint(stack,
                                                             monkeypatch):
    """End-to-end park loop on a mocker fleet: the frontend reaper calls
    the affinity worker's kv_session endpoint (the mocker stub answers
    blocks=0) and the session flips to parked; the returning turn fires
    the proactive restore and un-parks it."""
    monkeypatch.setenv("DYN_SESSION_PARK_AFTER_S", "0.3")
    monkeypatch.setenv("DYN_SESSION_REAP_INTERVAL_S", "0.1")
    rt = await DistributedRuntime.create()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
    service = HttpService(manager, port=0)
    await service.start()
    lease = await rt.plane.lease_create(30)
    (engine,), (handle,) = await run_mocker(rt, MODEL, mock_args(),
                                            lease_id=lease)
    try:
        await wait_for_model(manager)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as http:
            body = {"model": MODEL, "max_output_tokens": 4,
                    "input": "park me when I go idle"}
            async with http.post(f"{base}/v1/responses", json=body) as r:
                assert r.status == 200, await r.text()
                prev = (await r.json())["id"]

            async def parked_state():
                async with http.get(f"{base}/v1/sessions") as r:
                    snap = await r.json()
                return snap["sessions"][0] if snap["sessions"] else None

            for _ in range(100):                 # reaper parks after ~0.3s
                s = await parked_state()
                if s and s["parked"]:
                    break
                await asyncio.sleep(0.05)
            assert s and s["parked"]

            # the session returns: delta turn un-parks + fires restore
            body = {"model": MODEL, "max_output_tokens": 4,
                    "input": "I am back", "previous_response_id": prev}
            async with http.post(f"{base}/v1/responses", json=body) as r:
                assert r.status == 200, await r.text()
            s = await parked_state()
            assert s and not s["parked"] and s["turns"] == 2
    finally:
        await service.stop()
        await watcher.stop()
        await handle.stop(graceful=False)
        await engine.stop()
        await rt.shutdown()


async def test_trace_shapes_and_ttl_reaper_over_http(stack, monkeypatch):
    """The load tool's session shapes against a short-TTL frontend: an
    agent tool-loop session completes with its follow-up turns, an
    abandoned session walks away mid-conversation, and once the TTL has
    passed the reaper has collected both (``/v1/sessions`` counts 0)."""
    import random

    from benchmarks.client import run_session_trace, session_headers

    monkeypatch.setenv("DYN_SESSION_TTL_S", "1.2")
    monkeypatch.setenv("DYN_SESSION_REAP_INTERVAL_S", "0.15")
    rt, _service, add_mocker, manager = stack
    service = HttpService(manager, port=0)
    await service.start()
    try:
        await add_mocker()
        await wait_for_model(manager)
        base = f"http://127.0.0.1:{service.port}"
        rng = random.Random(7)
        shape = dict(rng=rng, words_per_turn=20, osl=8, think_s=(0.05, 0.1),
                     sampling={"temperature": 0.0})
        async with aiohttp.ClientSession() as http:
            agent = await run_session_trace(
                http, [base], MODEL, sid="agent", turns=3, tool_loop_p=1.0,
                headers=session_headers("agent"), **shape)
            gone = await run_session_trace(
                http, [base], MODEL, sid="gone", turns=4, abandon_p=1.0,
                headers=session_headers("gone"), **shape)
            assert agent.ok and agent.tool_loops > 0
            assert gone.abandoned
            async with http.get(f"{base}/v1/sessions") as r:
                assert (await r.json())["count"] >= 1
            for _ in range(100):  # TTL 1.2 s + a reap sweep
                async with http.get(f"{base}/v1/sessions") as r:
                    if (await r.json())["count"] == 0:
                        break
                await asyncio.sleep(0.05)
            else:
                raise AssertionError("abandoned session never reaped")
    finally:
        await service.stop()


async def test_sessions_disabled_is_stateless(stack, monkeypatch):
    """DYN_SESSIONS=0: no registry, /v1/sessions says disabled, and a
    previous_response_id is a typed 404 (never a silent fallback)."""
    monkeypatch.setenv("DYN_SESSIONS", "0")
    rt, service0, add_mocker, manager = stack
    service = HttpService(manager, port=0)
    await service.start()
    try:
        await add_mocker()
        await wait_for_model(manager)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as http:
            async with http.get(f"{base}/v1/sessions") as r:
                assert (await r.json())["enabled"] is False
            body = {"model": MODEL, "input": "hi", "max_output_tokens": 4,
                    "previous_response_id": "resp-x"}
            async with http.post(f"{base}/v1/responses", json=body) as r:
                assert r.status == 404
                assert (await r.json())["error"]["type"] == \
                    "previous_response_not_found"
    finally:
        await service.stop()
