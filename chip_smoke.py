#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # tensor-parallel path on a 4-chip host

One chip: starts the three-process fleet a deployment runs (README "Quick
start": ``dynctl`` hub, ``dynamo_tpu.engine.main`` worker, ``dynamo_tpu.
frontend.main`` OpenAI frontend) with ``--arch mistral_7b --quantization int8
--use-pallas-attention --warmup-buckets`` — published Mistral-7B widths, all
32 layers, random weights from the engine's seed, bf16 KV pages sized from
the chip's free memory — and drives it over HTTP: a streamed request,
staggered concurrent requests (a mixed prefill+decode step), a repeated
prefix (prefix-cache hit) and a greedy repeat. It checks exact
``completion_tokens``, repeatability, non-zero TTFT/ITL series on the
frontend's ``/metrics`` and an EMPTY ``dynamo_ragged_fallback_total`` on the
worker's. The fleet is then stopped, and only after the worker has released
the chip does this process touch JAX: it compiles the same jitted serving
step and asserts the Mosaic kernel is in it, and compares the kernel with
the ``ragged_attention_xla`` oracle at the serving widths.

A chip belongs to one process at a time, so the script itself stays off JAX
while the worker lives; the device is first learned from the worker's own
``engine built:`` log line.

Four chips (``--chips 4``) runs ONLY the tensor-parallel path and what it is
compared with: the same fleet at ``--tp-size 4`` in bf16 (XLA attention — a
mesh bypasses the kernel today, reason ``mesh``), sharding and per-device
bytes asserted from the worker's build facts, then in-process an 8-layer
tp=4 engine against an 8-layer one-device engine of the same weights.

Every line of stdout is one JSON object; the last one is the contract's.
Without a TPU the script exits non-zero and never prints ``"ok": true`` —
``JAX_PLATFORMS=cpu python chip_smoke.py --arch tiny`` rehearses every phase
and then fails at the device check.
"""

import argparse
import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # the fleet's logs
MODEL = "smoke"
WORDS = ("hello world the quick brown fox jumps over lazy dog a b c d e f g "
         "h i j what is capital of france paris tell me about tokens stream "
         "stop sequence test").split()  # llm/tokenizer.py make_test_tokenizer
#: engine geometry for the smoke: 6 token buckets (8..256) x 2 step variants
#: to warm instead of 9 x 2, room for every request below
GEOMETRY = dict(max_num_seqs=16, max_num_batched_tokens=256,
                max_model_len=1024)
GEOMETRY_FLAGS = [x for k, v in GEOMETRY.items()
                  for x in ("--" + k.replace("_", "-"), str(v))]


def emit(**fact):
    print(json.dumps(fact), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def prompt(seed: int, n_words: int) -> str:
    rng = random.Random(seed)
    return " ".join(rng.choice(WORDS) for _ in range(n_words))


class Fleet:
    """hub + engine worker + frontend as child processes, logs under WORK."""

    def __init__(self, worker_args: list):
        os.makedirs(WORK, exist_ok=True)
        self.procs: list = []
        self.hub_port, self.http_port = free_port(), free_port()
        self.sys_port = free_port()
        self.worker_args = worker_args
        self.env = dict(os.environ, PYTHONPATH=ROOT, PYTHONUNBUFFERED="1",
                        DYN_CONTROL_PLANE=f"127.0.0.1:{self.hub_port}",
                        DYN_LOG="info")

    def spawn(self, name: str, module: str, args: list, env=None):
        log = open(os.path.join(WORK, f"{name}.log"), "w")
        p = subprocess.Popen(
            [sys.executable, "-m", module, *args], cwd=ROOT,
            env=env or self.env, stdout=log, stderr=subprocess.STDOUT)
        p.log_path, p.name = log.name, name
        self.procs.append(p)
        return p

    def wait_for(self, p, marker: str, timeout: float) -> str:
        """Block until ``marker`` shows in the process's log; its death or
        the deadline is a failure (the log's tail goes to stderr)."""
        deadline = time.monotonic() + timeout
        while True:
            text = open(p.log_path).read()
            if marker in text:
                return text
            if p.poll() is not None or time.monotonic() > deadline:
                sys.stderr.write(text[-6000:])
                raise SystemExit(
                    f"{p.name}: no {marker!r} (exit code {p.poll()}, "
                    f"waited {timeout:.0f}s)")
            time.sleep(0.5)

    def start(self, ready_timeout: float) -> dict:
        t0 = time.monotonic()
        hub = self.spawn("hub", "dynamo_tpu.runtime.dynctl",
                         ["--port", str(self.hub_port)])
        self.wait_for(hub, "dynctl listening", 60)
        worker = self.spawn(
            "worker", "dynamo_tpu.engine.main",
            ["--model", MODEL, "--allow-test-metadata", "--warmup-buckets",
             *GEOMETRY_FLAGS, *self.worker_args],
            env=dict(self.env, DYN_SYSTEM_PORT=str(self.sys_port)))
        log = self.wait_for(worker, "WORKER_READY", ready_timeout)
        front = self.spawn("frontend", "dynamo_tpu.frontend.main",
                           ["--port", str(self.http_port)])
        self.wait_for(front, "FRONTEND_READY", 120)
        deadline = time.monotonic() + 60
        while MODEL not in http_get(self.http_port, "/v1/models"):
            if time.monotonic() > deadline:
                raise SystemExit("frontend never listed the worker's model")
            time.sleep(0.5)
        facts = json.loads(
            re.search(r"engine built: (\{.*\})", log).group(1))
        warm = re.search(
            r"ragged warmup: (\d+) token-bucket signatures in ([\d.]+)s", log)
        facts["warmed_signatures"] = int(warm.group(1))
        facts["compile_seconds"] = float(warm.group(2))
        facts["seconds_to_ready"] = round(time.monotonic() - t0, 1)
        return facts

    def stop(self):
        for p in reversed(self.procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in self.procs:
            try:
                p.wait(30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def http_get(port: int, path: str) -> str:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    c.request("GET", path)
    r = c.getresponse()
    body = r.read().decode()
    c.close()
    if r.status != 200:
        raise SystemExit(f"GET {path}: {r.status} {body[:300]}")
    return body


def chat(port: int, text: str, max_tokens: int, logprobs: bool = False,
         on_first=None):
    """One streamed greedy /v1/chat/completions; returns what the client
    saw: TTFT, chunk gaps, text, usage, and logprobs when asked.
    ``on_first`` is called when the first chunk arrives."""
    body = {"model": MODEL, "stream": True, "temperature": 0,
            "max_tokens": max_tokens, "ignore_eos": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": text}]}
    if logprobs:
        body["logprobs"] = True
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    t0 = time.monotonic()
    c.request("POST", "/v1/chat/completions", json.dumps(body),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    if r.status != 200:
        raise SystemExit(f"chat: HTTP {r.status} {r.read()[:500]!r}")
    out = {"ttft_s": None, "text": "", "usage": None, "logprobs": [],
           "finish": None, "chunk_times": []}
    for raw in r:
        line = raw.decode().strip()
        if not line.startswith("data:") or line == "data: [DONE]":
            continue
        chunk = json.loads(line[5:])
        if chunk.get("usage"):
            out["usage"] = chunk["usage"]
        for ch in chunk.get("choices", []):
            now = time.monotonic() - t0
            if out["ttft_s"] is None:
                out["ttft_s"] = round(now, 4)
                if on_first is not None:
                    on_first()
            out["chunk_times"].append(now)
            out["text"] += (ch.get("delta") or {}).get("content") or ""
            for e in ((ch.get("logprobs") or {}).get("content") or []):
                out["logprobs"].append(e["logprob"])
            out["finish"] = ch.get("finish_reason") or out["finish"]
    out["seconds"] = round(time.monotonic() - t0, 4)
    c.close()
    if out["usage"] is None:
        raise SystemExit("chat: stream ended without a usage block")
    if out["usage"]["completion_tokens"] != max_tokens:
        raise SystemExit(
            f"completion_tokens {out['usage']['completion_tokens']} != "
            f"max_tokens {max_tokens} (ignore_eos, finish={out['finish']})")
    return out


def metric_samples(text: str, name: str) -> dict:
    """{labels: value} of one family in a Prometheus text exposition."""
    found = {}
    for line in text.splitlines():
        m = re.match(rf"{re.escape(name)}(\{{[^}}]*\}})?\s+(\S+)$", line)
        if m:
            found[m.group(1) or ""] = float(m.group(2))
    return found


def request_fact(name: str, r: dict) -> dict:
    return {"request": name, "ttft_s": r["ttft_s"], "seconds": r["seconds"],
            "prompt_tokens": r["usage"]["prompt_tokens"],
            "completion_tokens": r["usage"]["completion_tokens"]}


def drive_http(fleet: Fleet, full: bool):
    """The request mix. ``full`` adds the concurrent / prefix / repeat
    phases (one chip); the four-chip phase sends the few it needs."""
    port = fleet.http_port
    first = chat(port, prompt(1, 40), 24)
    emit(**request_fact("streamed", first))
    if first["ttft_s"] is None or len(first["chunk_times"]) < 2:
        raise SystemExit("streamed request produced no incremental chunks")

    # the first request decodes for a while; the others arrive once its
    # first token is out, so their prompts prefill while it decodes and the
    # scheduler plans mixed prefill+decode ragged steps
    results: dict = {}
    decoding = threading.Event()

    def one(i, n_words, max_tokens):
        if i:
            decoding.wait(120)
            time.sleep(0.02 * i)
        results[i] = chat(port, prompt(10 + i, n_words), max_tokens,
                          on_first=None if i else decoding.set)

    mix = [(30, 96), (90, 32), (20, 40), (150, 24), (60, 32), (110, 16)]
    mix = mix if full else mix[:3]
    threads = [threading.Thread(target=one, args=(i, *m))
               for i, m in enumerate(mix)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if len(results) != len(mix):
        raise SystemExit("a concurrent request failed (see stderr)")
    for i in sorted(results):
        emit(**request_fact(f"concurrent-{i}", results[i]))
    if not full:
        return

    # prefix cache: the same 100-word prompt, then it again with a tail
    hits = lambda: sum(metric_samples(  # noqa: E731
        http_get(fleet.sys_port, "/metrics"),
        "dynamo_prefix_hit_tokens_total").values())
    base = prompt(77, 100)
    cold = chat(port, base, 16, logprobs=True)
    h0 = hits()
    warm1 = chat(port, base, 16, logprobs=True)
    warm2 = chat(port, base, 16, logprobs=True)
    tail = chat(port, base + " " + prompt(78, 20), 16)
    h1 = hits()
    emit(**request_fact("prefix-cold", cold))
    emit(**request_fact("prefix-warm", warm1))
    emit(**request_fact("prefix-tail", tail))
    if h1 - h0 < 3 * 64:
        raise SystemExit(f"prefix cache not hit: hit tokens {h0} -> {h1}")
    # greedy repeatability: warm1 and warm2 run the identical computation
    # (same cached prefix, same chunk, alone on the engine), so text AND
    # logprobs must repeat exactly. The cold run prefills the whole prompt
    # in another token bucket — bf16 matmuls tile differently there, so its
    # agreement is reported, not asserted.
    if (warm1["text"], warm1["logprobs"]) != (warm2["text"],
                                              warm2["logprobs"]):
        raise SystemExit("greedy repeat diverged between identical runs")
    if len(warm1["logprobs"]) != 16:
        raise SystemExit(f"expected 16 logprobs, got {warm1['logprobs']}")
    emit(phase="repeat", prefix_hit_tokens=h1 - h0, identical=True,
         cold_agrees=cold["logprobs"] == warm1["logprobs"],
         max_logprob_gap_cold=max(
             abs(a - b) for a, b in zip(cold["logprobs"],
                                        warm1["logprobs"])))


def check_steps(fleet: Fleet, expect_mixed: bool):
    """The workers' flight records, through the frontend: did a step carry
    decode rows AND a prefill chunk, and did any step compile while
    serving (warm-up should have left none: a compile inside a request is
    what a TTFT above must not be read as)."""
    doc = json.loads(http_get(fleet.http_port, "/v1/fleet/steps?n=4096"))
    steps = [s for w in doc["workers"].values() for s in w.get("steps", [])]
    mixed = sum(1 for s in steps
                if s.get("decode_rows") and s.get("prefill_chunks"))
    compiled = [s.get("compile_sig") for s in steps if s.get("compile_s")]
    wide = sum(s.get("wide_tile_rows", 0) for s in steps)
    emit(phase="steps", recorded=len(steps), mixed_prefill_decode=mixed,
         wide_tile_rows=wide, compiled_while_serving=compiled)
    if expect_mixed and not mixed:
        raise SystemExit("no step mixed decode rows with a prefill chunk")
    if not wide:
        raise SystemExit("no step record counts a row above the ragged "
                         "kernel's small query tile (every prompt here is)")
    if compiled:
        raise SystemExit(f"steps compiled while serving: {compiled}")


def check_metrics(fleet: Fleet, expect_fallback_empty: bool):
    front = http_get(fleet.http_port, "/metrics")
    worker = http_get(fleet.sys_port, "/metrics")
    ttft = metric_samples(front,
                          "dynamo_http_time_to_first_token_seconds_count")
    itl = metric_samples(front, "dynamo_itl_seconds_count")
    if not sum(ttft.values()) or not sum(itl.values()):
        raise SystemExit(f"frontend /metrics: ttft {ttft} itl {itl}")
    fallback = metric_samples(worker, "dynamo_ragged_fallback_total")
    steps = metric_samples(worker, "dynamo_engine_step_steps")
    wide = metric_samples(worker, "dynamo_ragged_wide_tile_rows_total")
    emit(phase="metrics", ttft_count=sum(ttft.values()),
         itl_count=sum(itl.values()), ragged_fallback_total=fallback,
         ragged_wide_tile_rows_total=sum(wide.values()),
         engine_steps_by_kind=steps)
    if expect_fallback_empty and any(fallback.values()):
        raise SystemExit(f"ragged fallbacks counted: {fallback}")


def probe_platform() -> str:
    """What JAX would give a process here — asked in a child that exits
    (and so releases the chip) before the fleet starts."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print('PLATFORM=' + jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300)
    m = re.search(r"PLATFORM=(\w+)", out.stdout)
    if out.returncode or not m:
        sys.stderr.write(out.stderr[-3000:])
        raise SystemExit("JAX found no device at all")
    return m.group(1)


def native_core() -> str:
    """The C++ hashing core is git-ignored, so a checkout never has it:
    build it from native/*.cc (the fleet's processes load it at import), or
    say that the pure-Python path is in use."""
    r = subprocess.run([sys.executable, "-m", "dynamo_tpu.native_build"],
                       cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH=ROOT))
    if r.returncode == 0:
        return "built from native/*.cc"
    so = os.path.join(ROOT, "dynamo_tpu", "libdynamo_native.so")
    return "prebuilt .so (no g++)" if os.path.exists(so) else "pure python"


def kernel_checks(arch: str, quantization):
    """In THIS process, after the fleet released the chip: the jitted
    serving step must contain the Mosaic kernel, and the kernel must agree
    with the XLA oracle at the serving widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.ops.ragged_attention import (
        ragged_attention_xla, ragged_paged_attention,
        ragged_pallas_supported,
    )

    cfg = get_model_config(arch)
    args = EngineArgs(quantization=quantization, use_pallas_attention=True,
                      **GEOMETRY)
    bs, T = args.block_size, 64
    R, W = args.ragged_rows(T), args.max_blocks_per_seq
    C, _ = M.ragged_grid_shape(T)
    nb = 256
    spec = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.key(0), quantization=quantization))
    cache = spec((cfg.num_layers, nb * bs, cfg.num_kv_heads, cfg.head_dim),
                 jnp.dtype(cfg.dtype))
    step = M.make_ragged_step_fn(cfg, bs, None, use_pallas=True)
    t0 = time.monotonic()
    text = step.lower(
        params, spec((5, T), jnp.int32), spec((R, 3), jnp.int32),
        spec((C,), jnp.int32), spec((R, W), jnp.int32), cache, cache
    ).compile().as_text()
    in_step = "tpu_custom_call" in text
    emit(phase="compiled_step", T=T, layers=cfg.num_layers,
         tpu_custom_call=in_step,
         compile_seconds=round(time.monotonic() - t0, 1))

    # kernel vs oracle: decode rows + two prefill chunks over random pages
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    rows = [(1, 200), (1, 17), (1, 1024), (40, 40), (21, 300)]
    Wk = 64
    rng = np.random.default_rng(0)
    rows3 = np.zeros((len(rows) + 2, 3), np.int32)
    bt = np.zeros((len(rows) + 2, Wk), np.int32)
    t = 0
    for i, (ql, kl) in enumerate(rows):
        rows3[i] = (t, ql, kl)
        used = -(-kl // bs)
        bt[i, :used] = rng.choice(np.arange(1, nb), used, replace=False)
        t += ql
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (t, H, hd), jnp.float32).astype(dt)
    kc = jax.random.normal(ks[1], (nb * bs, KV, hd), jnp.float32).astype(dt)
    vc = jax.random.normal(ks[2], (nb * bs, KV, hd), jnp.float32).astype(dt)
    kw = dict(block_size=bs, window=cfg.sliding_window)
    want = ragged_attention_xla(q, kc, vc, jnp.asarray(bt),
                                jnp.asarray(rows3), **kw)
    got = ragged_paged_attention(q, kc, vc, jnp.asarray(bt),
                                 jnp.asarray(rows3), **kw)
    diff = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))
    # both sides accumulate in f32 and round the result to the model dtype
    # once: they may differ by a rounding step of an O(1) output
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    emit(phase="kernel_vs_xla", H=H, KV=KV, hd=hd, tokens=t,
         kernel_on_path=ragged_pallas_supported(KV, hd, hd),
         max_abs_diff=diff, tolerance=tol)
    if not np.isfinite(diff) or diff > tol:
        raise SystemExit(f"kernel vs oracle: max abs diff {diff} > {tol}")
    return in_step


def device_line(expect_count: int, served: dict) -> dict:
    """The contract's device block, from JAX itself — and the reason the
    script cannot pass on the CPU."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if device["platform"] != "tpu":
        raise SystemExit(f"no accelerator: JAX runs on {device}")
    if device != served:
        raise SystemExit(f"served on {served}, but JAX here sees {device}")
    if device["count"] != expect_count:
        raise SystemExit(f"expected {expect_count} chips, found {device}")
    return device


def one_chip(arch: str):
    quantization = "int8"
    fleet = Fleet(["--arch", arch, "--quantization", quantization,
                   "--use-pallas-attention"])
    try:
        facts = fleet.start(ready_timeout=900)
        emit(phase="ready", arch=arch, quantization=quantization,
             entry="dynctl + engine.main + frontend.main", **facts)
        drive_http(fleet, full=True)
        check_steps(fleet, expect_mixed=True)
        check_metrics(fleet, expect_fallback_empty=facts["device"]
                      ["platform"] == "tpu")
    finally:
        fleet.stop()
    from dynamo_tpu.runtime.config import place_compile_cache

    emit(phase="compile_cache", dir=place_compile_cache())
    in_step = kernel_checks(arch, quantization)
    device = device_line(1, facts["device"])
    if not in_step or not facts["attention"].endswith("(Mosaic)"):
        raise SystemExit(f"kernel not on the path: {facts['attention']}, "
                         f"tpu_custom_call in step: {in_step}")
    return device


def four_chips(arch: str):
    fleet = Fleet(["--arch", arch, "--tp-size", "4"])
    try:
        facts = fleet.start(ready_timeout=1500)
        emit(phase="ready", arch=arch, tp=4,
             entry="dynctl + engine.main + frontend.main", **facts)
        drive_http(fleet, full=False)
        check_steps(fleet, expect_mixed=False)
        check_metrics(fleet, expect_fallback_empty=True)
    finally:
        fleet.stop()
    if facts["min_devices_per_leaf"] != 4:
        raise SystemExit("a parameter or cache leaf is not spread over four "
                         f"devices: {facts['min_devices_per_leaf']}")
    per_dev = facts["bytes_in_use_per_device"]
    whole = facts["weights_bytes"] + facts["kv_bytes"]
    # each chip holds a quarter of the sharded weights and pages, plus its
    # replica of the small leaves (norms, scales) and compiled programs:
    # within -5% / +25% of a quarter
    for b in per_dev:
        # (the CPU backend of a rehearsal reports no bytes at all)
        if b is not None and not 0.95 * whole / 4 <= b <= 1.25 * whole / 4:
            raise SystemExit(f"per-device bytes {per_dev} vs a quarter of "
                             f"{whole} = {whole // 4}")
    emit(phase="sharding", bytes_in_use_per_device=per_dev,
         quarter_of_whole=whole // 4)

    from dynamo_tpu.runtime.config import place_compile_cache

    emit(phase="compile_cache", dir=place_compile_cache())
    tp_vs_one_device(arch, layers=8)
    return device_line(4, facts["device"])


def tp_vs_one_device(arch: str, layers: int):
    """Same weights (same seed — init values do not depend on the mesh),
    depth cut so one chip holds them: greedy tokens and per-token logprobs
    of a tp=4 engine against a one-device engine."""
    import asyncio
    import dataclasses

    import jax

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from dynamo_tpu.protocols import (
        OutputOptions, PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = dataclasses.replace(get_model_config(arch), num_layers=layers)
    base = dict(GEOMETRY, num_blocks=512)
    prompts = [[(7 * i + 3 * j) % 40 + 3 for j in range(n)]
               for i, n in enumerate((12, 45, 90))]

    async def run(engine):
        outs = []
        for p in prompts:
            req = PreprocessedRequest(
                model=MODEL, token_ids=p, eos_token_ids=[],
                sampling_options=SamplingOptions(temperature=0.0),
                stop_conditions=StopConditions(max_tokens=12,
                                               ignore_eos=True),
                output_options=OutputOptions(logprobs=2))
            toks, tops = [], []
            async for out in engine.generate(req):
                toks += out.token_ids
                tops += [sorted((lp for _, lp in alts), reverse=True)
                         for alts in out.top_logprobs or []]
            outs.append((toks, tops))
        await engine.close()
        return outs

    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=4, pp=1))
    tp = asyncio.run(run(AsyncJaxEngine(
        cfg, EngineArgs(tp_size=4, **base), mesh=mesh)))
    one = asyncio.run(run(AsyncJaxEngine(cfg, EngineArgs(**base))))
    # Logits leave the model in bf16: near the top logit (~4 for a random
    # 32k-vocabulary head) one ulp is 0.031. tp=4 splits every wo / w_down
    # contraction into four partial sums that are all-reduced, so results
    # differ from one device's in the last bits — three ulps are allowed.
    # Greedy tokens must be identical up to the first step at which the
    # one-device arm's own top-2 were closer than that; the streams are
    # compared up to there.
    tol, gap, compared, total = 0.1, 0.0, 0, 0
    for (ta, la), (tb, lb) in zip(tp, one):
        if len(tb) != 12 or len(lb) != 12 or len(ta) != 12:
            raise SystemExit(f"short stream: {len(ta)}/{len(tb)} tokens, "
                             f"{len(lb)} logprob rows")
        n = next((i for i, (a, b) in enumerate(zip(ta, tb)) if a != b), 12)
        if n < 12 and lb[n][0] - lb[n][1] > tol:
            raise SystemExit(
                f"tp=4 picked another token at step {n} although one "
                f"device's top-2 were {lb[n][0] - lb[n][1]:.3f} nats apart")
        gap = max([gap] + [abs(a[0] - b[0])
                           for a, b in zip(la[:n], lb[:n])])
        compared += n
        total += 12
    emit(phase="tp4_vs_one_device", layers=layers, tokens=total,
         tokens_identical_until_near_tie=compared, max_logprob_gap=gap,
         tolerance=tol, devices=len(jax.devices()))
    if gap > tol or compared == 0:
        raise SystemExit(f"tp=4 disagrees with one device: {compared}/"
                         f"{total} tokens compared, logprob gap {gap}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--arch", default="mistral_7b",
                    help="model preset; 'tiny' rehearses on the CPU")
    cli = ap.parse_args()
    sys.path.insert(0, ROOT)
    platform = probe_platform()
    if platform != "tpu" and cli.arch != "tiny":
        raise SystemExit(
            f"no accelerator (JAX gives {platform!r}): not running "
            f"{cli.arch} at full width on it; rehearse with --arch tiny")
    emit(phase="start", chips=cli.chips, arch=cli.arch, platform=platform,
         native_core=native_core(),
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    device = one_chip(cli.arch) if cli.chips == 1 else four_chips(cli.arch)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
