"""Load generation and client-side arithmetic for chipbench.

One general generator reads a traffic mix (``chipbench/traffic/<mix>.json``,
the SHAPE) and a cell's ``params`` (``chipbench/cells/<workload>.json``, the
SCALE) and drives ``/v1/completions`` with token-id prompts over SSE:

- open loop (``"loop": "open"``): arrivals on a schedule whatever the server
  does; a request's clock starts when it was DUE, and how late the generator
  sent it is reported (``late_ms``);
- closed loop (``"loop": "closed"``): ``clients`` callers that each send the
  next request when the last one completes; the clock starts at the send.

A mix's trace (gaps, prompt and output lengths) is drawn from the mix's own
``base_seed`` and replayed as it is in every run; ``--seed`` draws the token
ids. So two seeds offer the same sizes at the same instants with other
contents, and runs differ by the system, not by the draw. (Measured on the
chip, PR 24: replaying the same trace from another starting point moved
``ttft_p50_ms`` by up to 9% where two runs from one starting point agreed to
0.2–3.4% — more than a bound of at most 10% can carry.)

Adapted from ``benchmarks/trace_replay.py`` (token-id prompts; its hash-id
blocks for shared prefixes are left for the cell that needs them) and
``benchmarks/client.py`` (SSE parsing, in-band errors, usage block),
corrected: due-time clocks, lateness reported, one gap per content-bearing
chunk with tokens per chunk stated beside it. Every prompt is unique.
The harness process stays off JAX: this file imports numpy and aiohttp only.
"""

from __future__ import annotations

import asyncio
import json
import math
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

INF = float("inf")


# ------------------------------------------------------------ the schedule

def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers from a length distribution of a mix file."""
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif kind == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", INF)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(n: int, span_s: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process, scaled to sum to
    exactly ``span_s`` (drawn as a gamma of shape 1, which is what the
    measured trace was drawn with: the draw stays bit for bit)."""
    g = rng.gamma(1.0, 1.0, n)
    return g * (span_s / g.sum())


@dataclass
class Request:
    idx: int
    phase: str              # ramp | window | tail (open loop); pool (closed)
    due_s: Optional[float]  # seconds after the ramp began; None = closed loop
    prompt_len: int
    max_tokens: int


def _trace(mix: dict, n: int, span_s: float) -> dict:
    """The mix's fixed trace of ``n`` requests over ``span_s`` seconds, from
    its ``base_seed`` alone: gaps, prompt and output lengths."""
    process = mix.get("arrivals", {}).get("process", "poisson")
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    base = np.random.default_rng(int(mix.get("base_seed", 0)))
    return {"gap": gaps(n, span_s, base),
            "prompt": draw(mix["prompt_tokens"], n, base),
            "out": draw(mix["output_tokens"], n, base)}


def _request(t: dict, i: int, idx: int, phase: str, due) -> Request:
    return Request(idx, phase, due, int(t["prompt"][i]), int(t["out"][i]))


def open_schedule(mix: dict, rate_rps: float, seconds: float) -> list:
    """Ramp, window and tail of an open-loop run: a pure function of its
    arguments. The window is the mix's fixed trace of round(rate × seconds)
    requests; the ramp is the last ``ramp_s`` worth of that trace's cycle
    (its gaps scaled to fill ``ramp_s``) and the tail its first ``tail_s``
    worth again: it keeps load on until the window's last requests have a
    first token. ``--seed`` does not enter: it draws the token ids
    (:func:`prompt_ids`), so every seed offers the same sizes at the same
    instants with other contents."""
    n = max(1, round(rate_rps * seconds))
    t = _trace(mix, n, seconds)
    ramp_s, tail_s = float(mix["ramp_s"]), float(mix.get("tail_s", 10.0))
    n_ramp = max(1, round(rate_rps * ramp_s))
    n_tail = max(1, round(rate_rps * tail_s))
    order = [(j - n_ramp) % n for j in range(n_ramp + n + n_tail)]
    g = t["gap"][order]
    g[:n_ramp] *= ramp_s / g[:n_ramp].sum()
    due = np.concatenate(([0.0], np.cumsum(g)[:-1]))   # first one at 0
    phase = ["ramp"] * n_ramp + ["window"] * n + ["tail"] * n_tail
    return [_request(t, i, j, phase[j], float(due[j]))
            for j, i in enumerate(order)]


def closed_pool(mix: dict, clients: int) -> list:
    """The sizes closed-loop callers draw from, in order: the mix's fixed
    trace of ``pool_per_client`` × clients requests, walked round and round
    (token ids are fresh each time round, so a second walk shares nothing
    with the first)."""
    n = clients * int(mix.get("pool_per_client", 8))
    t = _trace(mix, n, 1.0)
    return [_request(t, j, j, "pool", None) for j in range(n)]


# id streams: requests of different streams share no token block, whatever
# the seed. A run's probe, its shape warm-up, its measured window and each
# unmeasured pass before it (a rehearsal, a sweep's windows) take their own,
# so nothing sent in set-up is found in the prefix cache by the window.
PROBE, WARMUP, WINDOW, REHEARSAL = 0, 1, 2, 3


def prompt_ids(n: int, serial: int, seed: int, vocab: tuple,
               stream: int = WINDOW) -> list:
    """``n`` unique random token ids from (seed, stream, serial)."""
    rng = np.random.default_rng([int(seed), int(stream), int(serial)])
    return rng.integers(vocab[0], vocab[1], n).tolist()


def body_for(model: str, ids: list, max_tokens: int,
             logprobs: Optional[int] = None) -> bytes:
    body = {"model": model, "prompt": ids, "stream": True,
            "max_tokens": max_tokens, "ignore_eos": True, "temperature": 0,
            "stream_options": {"include_usage": True}}
    if logprobs is not None:
        body["logprobs"] = logprobs
    return json.dumps(body).encode()


# -------------------------------------------------------------- one stream

@dataclass
class Stream:
    """What the client saw of one request. Times are ``time.perf_counter``
    seconds; ``start_t`` is the due instant (open loop) or the send."""
    idx: int
    phase: str
    max_tokens: int
    prompt_len: int
    start_t: float = 0.0
    sent_t: float = 0.0
    chunk_t: list = field(default_factory=list)   # content-bearing chunks
    completion_tokens: Optional[int] = None       # the server's usage block
    prompt_tokens: Optional[int] = None
    error: Optional[str] = None
    finished: bool = False      # [DONE] seen
    cut: bool = False           # closed by the client at the run's end
    logprobs: list = field(default_factory=list)  # with logprobs only
    top2_gap: list = field(default_factory=list)
    on_first: Optional[callable] = None           # called at the first chunk

    @property
    def failed(self) -> bool:
        if self.error is not None:
            return True
        if self.finished:
            return self.completion_tokens != self.max_tokens
        return not self.chunk_t      # cut before any token: missed it all

    @property
    def ttft(self) -> float:
        if self.failed or not self.chunk_t:
            return INF
        return self.chunk_t[0] - self.start_t


async def stream_one(session, url: str, body: bytes, s: Stream,
                     want_logprobs: bool = False):
    """POST one streamed completion and record chunk arrival times.

    A chunk is content-bearing if it has a ``choices`` entry (with random
    weights a token may detokenize to empty text, so the text is not
    tested); the last such chunk also carries ``usage``. Chunks are only
    parsed where needed (usage, errors, logprobs): the generator shares its
    machine's cores with the server."""
    s.sent_t = time.perf_counter()
    try:
        async with session.post(
                url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"}) as resp:
            if resp.status != 200:
                s.error = f"http {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.perf_counter()
                if raw.startswith(b"data: [DONE]"):
                    s.finished = True
                    break
                has_usage = b'"usage"' in raw
                if b'"error"' in raw or has_usage or want_logprobs:
                    chunk = json.loads(raw[5:])
                    if chunk.get("error"):
                        err = chunk["error"]
                        s.error = "in-band: " + str(
                            err.get("message", err)
                            if isinstance(err, dict) else err)[:200]
                        return
                    usage = chunk.get("usage")
                    if usage:
                        s.completion_tokens = usage.get("completion_tokens")
                        s.prompt_tokens = usage.get("prompt_tokens")
                    for ch in chunk.get("choices", []):
                        lp = ch.get("logprobs") or {}
                        picked = lp.get("token_logprobs") or []
                        s.logprobs += picked
                        # /v1/completions keys the alternatives by decoded
                        # text; where the top two decode alike the second
                        # overwrites the first, so: picked − the lowest left
                        for own, top in zip(picked,
                                            lp.get("top_logprobs") or []):
                            s.top2_gap.append(own - min(top.values())
                                              if top else INF)
                    if not chunk.get("choices"):
                        continue
                elif b'"choices"' not in raw:
                    continue
                s.chunk_t.append(now)
                if s.on_first is not None and len(s.chunk_t) == 1:
                    s.on_first()
    except asyncio.CancelledError:
        s.cut = True
        raise
    except Exception as e:  # connection refused/reset, broken stream
        s.error = f"client: {e!r}"[:200]


# ----------------------------------------------------------------- runners

@dataclass
class Run:
    """One run's client-side record, and the window it was taken in."""
    loop: str
    streams: list
    window: tuple            # (start, end) in perf_counter seconds
    window_epoch: tuple      # the same two instants in epoch seconds
    late_s: list             # send − due of the window's requests (open)
    hooks: dict              # what on_window_start / on_window_end returned


async def _call(hook):
    return await hook() if hook is not None else None


def _session():
    import aiohttp

    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=None))


async def _cancel(tasks):
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def run_open(url: str, model: str, mix: dict, schedule: list,
                   seconds: float, seed: int, *, stream: int = WINDOW,
                   on_window_start=None, on_window_end=None,
                   first_token_grace_s: float = 30.0) -> Run:
    """Send ``schedule`` on its clock. The window is [ramp_s, ramp_s +
    seconds) after the start; it ends on the clock, whatever is in flight.
    After it, the tail's load stays on until every request of the window has
    a first token (or ``first_token_grace_s`` is over); then the client
    closes what is still open."""
    vocab = tuple(mix["vocab"])
    ramp_s = float(mix["ramp_s"])
    streams, tasks, late = [], [], []
    hooks = {}
    async with _session() as session:
        t0 = time.perf_counter()
        w0, w1 = t0 + ramp_s, t0 + ramp_s + seconds
        epoch0 = time.time() + (w0 - time.perf_counter())

        async def at(when, hook, key):
            await asyncio.sleep(max(0.0, when - time.perf_counter()))
            hooks[key] = await _call(hook)

        side = [asyncio.ensure_future(at(w0, on_window_start, "start")),
                asyncio.ensure_future(at(w1, on_window_end, "end"))]
        window_streams = []
        for req in schedule:
            due = t0 + req.due_s
            if req.phase == "tail" and all(
                    s.chunk_t or s.error for s in window_streams):
                break
            body = body_for(model, prompt_ids(req.prompt_len, req.idx, seed,
                                              vocab, stream),
                            req.max_tokens)
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            s = Stream(req.idx, req.phase, req.max_tokens, req.prompt_len,
                       start_t=due)
            streams.append(s)
            if req.phase == "window":
                window_streams.append(s)
                late.append(max(0.0, time.perf_counter() - due))
            tasks.append(asyncio.ensure_future(
                stream_one(session, url, body, s)))
        deadline = w1 + first_token_grace_s
        while time.perf_counter() < deadline and not all(
                s.chunk_t or s.error or s.finished for s in window_streams):
            await asyncio.sleep(0.05)
        await asyncio.gather(*side)
        await _cancel(tasks)
    return Run("open", streams, (w0, w1), (epoch0, epoch0 + seconds), late,
               hooks)


async def run_closed(url: str, model: str, mix: dict, pool: list,
                     clients: int, seconds: float, seed: int, *,
                     stream: int = WINDOW,
                     on_window_start=None, on_window_end=None,
                     ramp_timeout_s: float = 120.0,
                     first_token_grace_s: float = 30.0) -> Run:
    """``clients`` callers, started staggered over ``stagger_s``; the window
    opens once each has had a first token and lasts ``seconds``. A request
    belongs to the window if it was SENT inside it."""
    vocab = tuple(mix["vocab"])
    stagger = float(mix.get("stagger_s", 4.0))
    streams, hooks = [], {}
    serial = iter(range(1 << 62))
    stop = asyncio.Event()
    first = [asyncio.Event() for _ in range(clients)]
    async with _session() as session:

        async def client(c: int):
            await asyncio.sleep(stagger * c / clients)
            while not stop.is_set():
                i = next(serial)
                req = pool[i % len(pool)]
                body = body_for(model, prompt_ids(req.prompt_len, i, seed,
                                                  vocab, stream),
                                req.max_tokens)
                s = Stream(i, "pool", req.max_tokens, req.prompt_len,
                           on_first=first[c].set)
                streams.append(s)
                s.start_t = time.perf_counter()
                await stream_one(session, url, body, s)
                first[c].set()
                if s.error:   # do not hammer a server that refuses
                    await asyncio.sleep(0.5)

        tasks = [asyncio.ensure_future(client(c)) for c in range(clients)]
        try:
            await asyncio.wait_for(
                asyncio.gather(*(e.wait() for e in first)), ramp_timeout_s)
        except asyncio.TimeoutError:
            await _cancel(tasks)
            raise SystemExit(f"ramp: not every client had a first token "
                             f"within {ramp_timeout_s:.0f}s")
        w0 = time.perf_counter()
        epoch0 = time.time()
        hooks["start"] = await _call(on_window_start)
        await asyncio.sleep(max(0.0, w0 + seconds - time.perf_counter()))
        w1 = w0 + seconds
        hooks["end"] = await _call(on_window_end)
        # the callers keep cycling until every request sent inside the
        # window has its first token: none is cut before it
        deadline = w1 + first_token_grace_s
        while time.perf_counter() < deadline and not all(
                s.chunk_t or s.error or s.finished for s in streams
                if s.sent_t < w1):
            await asyncio.sleep(0.05)
        stop.set()
        await _cancel(tasks)
    for s in streams:
        s.phase = "window" if w0 <= s.sent_t < w1 else "outside"
    return Run("closed", streams, (w0, w1), (epoch0, epoch0 + seconds), [],
               hooks)


async def warm_shapes(url: str, model: str, vocab: tuple, spec: dict,
                      resend_after_s: float = 0.0):
    """Set-up traffic that takes the server through every step shape the
    window can use, so that what compiles lazily on first use compiles
    before the window and not inside it. ``spec`` is a configuration's
    ``warmup``:

    - ``prompt_tokens``: one request per token bucket, one after another (a
      prefill step in each bucket);
    - ``groups``: for each size k, k short requests that decode for
      ``group_out`` tokens and, once they do, a ``long_prompt`` of several
      chunks: its mid-prompt chunk steps sample only the k decoding rows, a
      batch the sampler compiles per power of two (the engine's own warm-up
      covers 8 rows and up, and only whole steps);
    - ``burst``: that many concurrent short requests whose output lengths
      step up by one, so the decode batch walks down through every row
      count; they start ``stagger_s`` apart.

    ``resend_after_s`` > 0: a request with no first token after that long is
    closed and sent again (at most twice). Returns the seconds each part
    took. Any failure ends the run."""
    streams, resent = [], [0]

    async def one(session, n_prompt: int, n_out: int, delay: float = 0.0):
        req = Request(len(streams), "warmup", None, n_prompt, n_out)
        body = body_for(model, prompt_ids(n_prompt, req.idx, 777, vocab,
                                          WARMUP), n_out)
        if delay:
            await asyncio.sleep(delay)
        for attempt in range(3):
            s = Stream(req.idx, "warmup", n_out, n_prompt)
            task = asyncio.ensure_future(stream_one(session, url, body, s))
            # a request whose dispatch ack this program loses waits out its
            # 10 s request timeout (PERF.md, PR 24): set-up does not wait
            # with it but sends the request again, at most twice
            give_up = time.perf_counter() + resend_after_s
            while resend_after_s and attempt < 2 and not task.done() \
                    and not s.chunk_t and time.perf_counter() < give_up:
                await asyncio.sleep(0.02)
            if not resend_after_s or attempt == 2 or task.done() \
                    or s.chunk_t:
                await task
                break
            resent[0] += 1
            await _cancel([task])
        streams.append(s)

    short = spec.get("burst_prompt", 16)
    # requests that arrive in the same instant can lose their dispatch ack
    # in this program and then wait out its 10 s request timeout (PERF.md,
    # PR 24): set-up traffic keeps them ``stagger_s`` apart
    gap = float(spec.get("stagger_s", 0.03))
    took, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        took[name], t0 = time.perf_counter() - t0, time.perf_counter()

    async with _session() as session:
        for n in spec["prompt_tokens"]:
            await one(session, n, 4)
        lap("prompt_tokens")
        for k in spec.get("groups", []):
            await asyncio.gather(
                *(one(session, short, spec.get("group_out", 48),
                      delay=gap * i) for i in range(k)),
                one(session, spec["long_prompt"], 4, delay=0.25))
        lap("groups")
        await asyncio.gather(*(
            one(session, short, spec.get("burst_out_min", 8) + i,
                delay=gap * i) for i in range(spec.get("burst", 0))))
        lap("burst")
    bad = [s for s in streams if s.failed]
    if bad:
        raise SystemExit(f"warm-up: {len(bad)} of {len(streams)} requests "
                         f"failed: {bad[0].error}")
    return dict(took, requests=len(streams), resent=resent[0])


# -------------------------------------------------------------- arithmetic

def percentile(values: list, p: float) -> float:
    """Linear interpolation between order statistics (numpy's default);
    +inf entries (failures) stay +inf when the percentile reaches them."""
    if not values:
        return float("nan")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if xs[hi] == INF:
        return INF
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def summarize(run: Run) -> dict:
    """The client-side numbers of a run, in seconds and counts.

    - ``ttft``: over requests due (sent) inside the window, failures +inf;
    - ``gaps``: between successive content-bearing chunks of one stream, all
      streams pooled, gaps that END inside the window — one per chunk;
    - ``tokens``: content-bearing chunks received inside the window times
      the tokens per chunk of the streams that ended (the server's
      ``usage.completion_tokens`` over their chunks: 1.0 where the frontend
      sends a chunk per token)."""
    w0, w1 = run.window
    mine = [s for s in run.streams if s.phase == "window"]
    ended = [s for s in run.streams if s.finished and not s.error]
    n_chunks = sum(len(s.chunk_t) for s in ended)
    n_tokens = sum(s.completion_tokens or 0 for s in ended)
    per_chunk = n_tokens / n_chunks if n_chunks else float("nan")
    gaps_in, chunks_in = [], 0
    for s in run.streams:
        ts = s.chunk_t
        chunks_in += sum(1 for t in ts if w0 <= t < w1)
        gaps_in += [b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1]
    wrong = [s for s in run.streams
             if s.error or (s.finished
                            and s.completion_tokens != s.max_tokens)]
    return {
        "attempted": len(mine),
        "failed": sum(1 for s in mine if s.failed),
        "ttft_s": [s.ttft for s in mine],
        "gaps_s": gaps_in,
        "gap_percentiles_ms": {str(p): 1000.0 * percentile(gaps_in, p)
                               for p in (50, 90, 95, 98, 99)},
        "gap_mean_ms": 1000.0 * sum(gaps_in) / max(1, len(gaps_in)),
        "ttft_percentiles_ms": {str(p): 1000.0 * percentile(
            [s.ttft for s in mine], p) for p in (25, 50, 75, 90, 95)},
        "chunks_in_window": chunks_in,
        "tokens_per_chunk": per_chunk,
        "tokens_in_window": chunks_in * per_chunk,
        "window_s": w1 - w0,
        "late_s": run.late_s,
        "streams_total": len(run.streams),
        "streams_ended": len(ended),
        "streams_wrong": len(wrong),
        "errors": [s.error for s in run.streams if s.error][:5],
        "prompt_tokens_in_window": sum(s.prompt_len for s in mine),
        "open_at_window_end": sum(
            1 for s in run.streams if s.sent_t < w1 and not s.error
            and not (s.finished and s.chunk_t and s.chunk_t[-1] < w1)),
    }
