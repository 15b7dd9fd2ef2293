"""Shared async OpenAI benchmarking client: streaming requests with TTFT/ITL
measurement (the genai-perf-style core the harnesses build on — ref:
benchmarks/utils/ in the reference)."""

from __future__ import annotations

import asyncio
import random
import time
from dataclasses import dataclass, field
from typing import Optional

import aiohttp


@dataclass
class RequestResult:
    ok: bool
    prompt_tokens: int = 0
    ttft_s: Optional[float] = None
    latency_s: Optional[float] = None
    itl_s: list = field(default_factory=list)
    tokens: int = 0
    #: server-reported usage.completion_tokens — the EXACT count (client-
    #: side ``tokens`` undercounts when coalesced emission packs several
    #: tokens into one SSE delta); the autoscale bench's zero-loss
    #: accounting reads this
    completion_tokens: int = 0
    error: Optional[str] = None
    #: front-door failover accounting (stream_request_ha): total attempts
    #: made and the URL that produced this result
    attempts: int = 1
    url: Optional[str] = None
    #: responses-API extras (stream_responses_request): the response id
    #: (the next delta turn's previous_response_id) and the full text —
    #: the sessions bench's bit-identity check compares these across arms
    response_id: Optional[str] = None
    text: str = ""


def make_prompt(rng: random.Random, n_words: int, prefix: str = "") -> str:
    body = " ".join(f"w{rng.randrange(10_000)}" for _ in range(n_words))
    return (prefix + " " + body) if prefix else body


class Mix:
    """Weighted categorical sampler for ``--tenant-mix``/``--priority-mix``
    CLI values (``"interactive=0.6,batch=0.4"`` or bare ``"a,b"`` for
    uniform). Deterministic given the caller's seeded rng."""

    def __init__(self, spec: str):
        self.choices: list[tuple[str, float]] = []
        total = 0.0
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            name, _, w = part.partition("=")
            try:
                weight = float(w) if w else 1.0
            except ValueError:
                raise ValueError(
                    f"bad mix component {part!r} (want name=weight)") from None
            if weight < 0:
                raise ValueError(f"mix weight for {name!r} must be >= 0")
            self.choices.append((name.strip(), weight))
            total += weight
        if self.choices and total <= 0:
            raise ValueError(f"mix {spec!r} has zero total weight")
        self._total = total

    def __bool__(self) -> bool:
        return bool(self.choices)

    def pick(self, rng: random.Random) -> Optional[str]:
        if not self.choices:
            return None
        x = rng.random() * self._total
        for name, w in self.choices:
            x -= w
            if x <= 0:
                return name
        return self.choices[-1][0]


def session_headers(session_id: Optional[str],
                    tenant: Optional[str] = None,
                    priority: Optional[str] = None) -> dict:
    """QoS headers + the session identity header (docs/sessions.md).

    ``x-dynamo-session`` buys router affinity and idle-KV parking for every
    turn that carries it — INCLUDING failover retries: pass the result as
    ``headers=`` to ``stream_request_ha``/``stream_responses_ha`` and every
    attempt re-sends it, so a killed frontend cannot strand the session's
    affinity on the replica that died."""
    h = qos_headers(tenant, priority)
    if session_id:
        h["x-dynamo-session"] = session_id
    return h


def qos_headers(tenant: Optional[str], priority: Optional[str]) -> dict:
    """The QoS wire headers (docs/qos.md). NB: anonymous priority can only
    LOWER the class below the tenant's configured default — escalating to
    ``interactive`` needs the tenant configured with that class
    (``DYN_QOS_TENANTS``) or an API key."""
    h = {}
    if tenant:
        h["x-dynamo-tenant"] = tenant
    if priority:
        h["x-dynamo-priority"] = priority
    return h


async def stream_request(session: aiohttp.ClientSession, url: str, model: str,
                         prompt: str, max_tokens: int,
                         headers: Optional[dict] = None) -> RequestResult:
    t0 = time.perf_counter()
    res = RequestResult(ok=False)
    try:
        async with session.post(
            f"{url}/v1/chat/completions",
            json={"model": model, "stream": True, "ignore_eos": True,
                  "max_tokens": max_tokens,
                  "stream_options": {"include_usage": True},
                  "messages": [{"role": "user", "content": prompt}]},
            headers=headers or {},
        ) as resp:
            if resp.status != 200:
                res.error = f"http {resp.status}"
                return res
            import json as _json

            last = None
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                try:
                    chunk = _json.loads(line[6:])
                except ValueError:
                    continue
                if chunk.get("error"):
                    # in-band SSE error (stream broke after the 200 went
                    # out): the request FAILED even though the HTTP layer
                    # looks clean — counting it ok hides silent truncation
                    err = chunk["error"]
                    res.error = (err.get("message", "stream error")
                                 if isinstance(err, dict) else str(err))
                    break
                if chunk.get("usage"):  # record the true token ISL/OSL
                    res.prompt_tokens = chunk["usage"].get("prompt_tokens", 0)
                    res.completion_tokens = chunk["usage"].get(
                        "completion_tokens", 0)
                # only content-bearing chunks count as tokens — a
                # usage-only final chunk (vLLM/OpenAI emit one with empty
                # choices) must not inflate token counts or ITL samples
                if not any((c.get("delta") or {}).get("content")
                           or c.get("text") for c in chunk.get("choices", [])):
                    continue
                now = time.perf_counter()
                if res.ttft_s is None:
                    res.ttft_s = now - t0
                elif last is not None:
                    res.itl_s.append(now - last)
                last = now
                res.tokens += 1
            res.latency_s = time.perf_counter() - t0
            res.ok = res.ttft_s is not None and res.error is None
            return res
    except Exception as e:
        res.error = repr(e)
        return res


def _retryable(res: RequestResult) -> bool:
    """Failures worth re-driving at ANOTHER replica: connection refused/
    reset, a stream the peer's death broke mid-decode, a draining 503, or
    an overloaded 429. Deterministic client errors (400/404/401…) are NOT
    — they would fail identically everywhere."""
    if res.ok:
        return False
    err = res.error or ""
    if err.startswith("http "):
        return err in ("http 429", "http 503")
    return True


async def stream_request_ha(session: aiohttp.ClientSession, urls: list[str],
                            model: str, prompt: str, max_tokens: int,
                            headers: Optional[dict] = None,
                            max_attempts: int = 4,
                            backoff_s: float = 0.25,
                            start: int = 0) -> RequestResult:
    """Client-transparent front-door failover (docs/robustness.md "Front
    door"): drive ``stream_request`` against a list of frontend replica
    URLs, retrying refused/broken streams on the next replica with bounded
    attempts. Token accounting stays EXACT: a retry restarts the stream
    from scratch and only the final attempt's tokens/usage are kept — the
    killed frontend's worker-side seqs are cancelled via response-plane
    peer death, so the abandoned attempt serves nothing the client counts.
    ``start`` offsets the first URL so concurrent callers spread load."""
    urls = [u for u in urls if u]
    res = RequestResult(ok=False, error="no frontend urls")
    for attempt in range(max_attempts):
        url = urls[(start + attempt) % len(urls)]
        res = await stream_request(session, url, model, prompt, max_tokens,
                                   headers=headers)
        res.attempts = attempt + 1
        res.url = url
        if res.ok or not _retryable(res):
            return res
        if attempt + 1 < max_attempts:
            await asyncio.sleep(backoff_s * (attempt + 1))
    return res


async def stream_responses_request(session: aiohttp.ClientSession, url: str,
                                   model: str, input_items, max_tokens: int,
                                   previous_response_id: Optional[str] = None,
                                   headers: Optional[dict] = None,
                                   sampling: Optional[dict] = None
                                   ) -> RequestResult:
    """Stream one /v1/responses turn; TTFT/ITL keyed on output_text deltas.

    ``input_items`` is a string or a message-item list. With
    ``previous_response_id`` the items are the TURN DELTA — the frontend's
    session registry reconstructs the full conversation server-side
    (docs/sessions.md). The result carries ``response_id`` (the next
    delta's resume point) and the full ``text`` (bit-identity checks)."""
    t0 = time.perf_counter()
    res = RequestResult(ok=False)
    body = {"model": model, "stream": True, "input": input_items,
            "max_output_tokens": max_tokens}
    if previous_response_id is not None:
        body["previous_response_id"] = previous_response_id
    for k, v in (sampling or {}).items():
        body[k] = v
    try:
        async with session.post(f"{url}/v1/responses", json=body,
                                headers=headers or {}) as resp:
            if resp.status != 200:
                res.error = f"http {resp.status}"
                return res
            import json as _json

            last = None
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                try:
                    ev = _json.loads(line[6:])
                except ValueError:
                    continue
                typ = ev.get("type")
                if typ == "response.output_text.delta" and ev.get("delta"):
                    now = time.perf_counter()
                    if res.ttft_s is None:
                        res.ttft_s = now - t0
                    elif last is not None:
                        res.itl_s.append(now - last)
                    last = now
                    res.tokens += 1
                elif typ in ("response.completed", "response.incomplete"):
                    r = ev.get("response") or {}
                    res.response_id = r.get("id")
                    out = r.get("output") or []
                    if out and out[0].get("content"):
                        res.text = out[0]["content"][0].get("text", "")
                    u = r.get("usage") or {}
                    res.prompt_tokens = u.get("input_tokens", 0)
                    res.completion_tokens = u.get("output_tokens", 0)
                elif typ == "response.failed":
                    res.error = "response.failed"
                    break
            res.latency_s = time.perf_counter() - t0
            res.ok = res.ttft_s is not None and res.error is None
            return res
    except Exception as e:
        res.error = repr(e)
        return res


async def stream_responses_ha(session: aiohttp.ClientSession,
                              urls: list[str], model: str, input_items,
                              max_tokens: int,
                              previous_response_id: Optional[str] = None,
                              headers: Optional[dict] = None,
                              max_attempts: int = 4,
                              backoff_s: float = 0.25,
                              start: int = 0,
                              sampling: Optional[dict] = None
                              ) -> RequestResult:
    """``stream_request_ha`` for the responses route: caller-supplied
    headers (the session identity included) and the previous_response_id
    ride EVERY retry attempt, so a frontend kill mid-session neither
    strands the session's affinity nor silently downgrades a delta turn
    to a context-free one. NB: an unknown previous_response_id on the
    surviving replica is a deterministic 404 — _retryable correctly stops
    there instead of hammering replicas that will all refuse."""
    urls = [u for u in urls if u]
    res = RequestResult(ok=False, error="no frontend urls")
    for attempt in range(max_attempts):
        url = urls[(start + attempt) % len(urls)]
        res = await stream_responses_request(
            session, url, model, input_items, max_tokens,
            previous_response_id=previous_response_id, headers=headers,
            sampling=sampling)
        res.attempts = attempt + 1
        res.url = url
        if res.ok or not _retryable(res):
            return res
        if attempt + 1 < max_attempts:
            await asyncio.sleep(backoff_s * (attempt + 1))
    return res


@dataclass
class SessionResult:
    """One driven conversation (run_session_trace)."""

    sid: str
    turns: list = field(default_factory=list)  # RequestResult per turn
    abandoned: bool = False
    tool_loops: int = 0

    @property
    def ok(self) -> bool:
        return all(t.ok for t in self.turns) and bool(self.turns)


async def run_session_trace(session: aiohttp.ClientSession, urls: list[str],
                            model: str, *, sid: str, rng: random.Random,
                            turns: int, words_per_turn: int, osl: int,
                            think_s: tuple[float, float] = (0.5, 2.0),
                            tool_loop_p: float = 0.0,
                            abandon_p: float = 0.0,
                            delta: bool = True,
                            headers: Optional[dict] = None,
                            first_prompt: Optional[str] = None,
                            sampling: Optional[dict] = None,
                            max_attempts: int = 4,
                            on_turn=None) -> SessionResult:
    """Drive one session-realistic conversation (docs/sessions.md):
    think-time gaps between turns (uniform over ``think_s`` — real users
    read before they reply), tool loops (with prob ``tool_loop_p`` a turn
    is followed immediately by a near-zero-think follow-up, the agent-loop
    shape), and abandonment (with prob ``abandon_p`` the session walks
    away mid-conversation and never returns — reaper fodder).

    ``delta=True`` is the session-native arm: turn N+1 ships only the new
    user item + ``previous_response_id``. ``delta=False`` is the
    sessionless control: the full transcript rides every turn. Both arms
    produce byte-identical conversations under greedy sampling, which is
    what ``tests/test_sessions.py`` holds the two arms to."""
    out = SessionResult(sid=sid)
    transcript: list[dict] = []  # client-side mirror of the conversation
    prev_id: Optional[str] = None
    t = 0
    while t < turns:
        user_text = (first_prompt if (t == 0 and first_prompt is not None)
                     else make_prompt(rng, words_per_turn, prefix=f"turn{t}"))
        new_item = {"role": "user", "content": user_text}
        if delta and prev_id is not None:
            input_items = [new_item]
        else:
            input_items = transcript + [new_item]
        res = await stream_responses_ha(
            session, urls, model, input_items, osl,
            previous_response_id=prev_id if delta else None,
            headers=headers, start=rng.randrange(len(urls) or 1),
            max_attempts=max_attempts, sampling=sampling)
        out.turns.append(res)
        if on_turn is not None:
            on_turn(t, res)
        if not res.ok:
            break
        transcript.append(new_item)
        transcript.append({"role": "assistant", "content": res.text})
        prev_id = res.response_id
        t += 1
        if t >= turns:
            break
        if rng.random() < abandon_p:
            out.abandoned = True
            break
        if tool_loop_p and rng.random() < tool_loop_p:
            out.tool_loops += 1  # agent loop: immediate follow-up
            await asyncio.sleep(0.01)
        else:
            await asyncio.sleep(rng.uniform(*think_s))
    return out


async def run_closed_loop(url: str, model: str, *, concurrency: int,
                          num_requests: int, isl_words: int, osl: int,
                          prefix: str = "", seed: int = 0) -> list[RequestResult]:
    """Closed-loop load: ``concurrency`` workers issue requests back-to-back."""
    rng = random.Random(seed)
    prompts = [make_prompt(rng, isl_words, prefix) for _ in range(num_requests)]
    q: asyncio.Queue = asyncio.Queue()
    for p in prompts:
        q.put_nowait(p)
    results: list[RequestResult] = []

    async with aiohttp.ClientSession() as session:
        async def worker():
            while True:
                try:
                    p = q.get_nowait()
                except asyncio.QueueEmpty:
                    return
                results.append(
                    await stream_request(session, url, model, p, osl))

        await asyncio.gather(*(worker() for _ in range(concurrency)))
    return results


def summarize(results: list[RequestResult]) -> dict:
    import numpy as np

    ok = [r for r in results if r.ok]
    ttfts = sorted(r.ttft_s for r in ok)
    itls = [x for r in ok for x in r.itl_s]
    total_tokens = sum(r.tokens for r in ok)
    wall = max((r.latency_s or 0) for r in ok) if ok else 0
    return {
        "requests": len(results),
        "ok": len(ok),
        "ttft_p50_ms": round(1e3 * float(np.percentile(ttfts, 50)), 2) if ttfts else None,
        "ttft_p95_ms": round(1e3 * float(np.percentile(ttfts, 95)), 2) if ttfts else None,
        "itl_p50_ms": round(1e3 * float(np.percentile(itls, 50)), 2) if itls else None,
        "tokens": total_tokens,
    }
