#!/usr/bin/env python3
"""chipbench: one run of one benchmark cell on the chip, over the HTTP path.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run = start the fleet a deployment runs (``dynctl`` hub + engine worker +
OpenAI frontend, three processes) → probe → ramp → a window of ``--seconds``
→ read counters → stop the fleet → print. Every line of stdout is one JSON
object; the LAST is the contract's: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics (the worker
then traces the window's last seconds, up to its end). Logs, the client
record and the reduced trace go under ``chiprun_out/chipbench/<workload>/``.

Everything that belongs to one cell is DATA found by name; this file holds
no table of known names:

    BENCHMARK.json                  cells, metrics, which cells report which
    chipbench/configs/<config>.json the model's sizes + the worker's flags
    chipbench/traffic/<mix>.json    the traffic's SHAPE
    chipbench/cells/<workload>.json the SCALE of that mix in this cell
    chipbench/layer_metrics/<m>.py  one reader per per-layer metric

This process never touches JAX while the worker lives (a chip belongs to one
process); the device is learned from the worker's ``engine built:`` line, and
a run that did not serve from a TPU with the cell's chip count exits non-zero
and prints no result. Rehearsal on the CPU (every phase, then non-zero at the
device check, as ``chip_smoke.py`` does):

    JAX_PLATFORMS=cpu python chipbench/run.py \\
        --manifest chipbench/tests/rehearsal.json \\
        --workload tiny-cpu.chat-steady --seed 1 --seconds 10 --trace 0

and the four-chip flags with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and
``--workload tiny-cpu-tp4.decode-saturated``.

The sweep that fixes an open-loop cell's rate (one fleet start, successive
windows, a drain between):

    python3 chipbench/run.py --workload <name> --seed 1 --seconds 30 \\
        --sweep 1,1.5,2,2.5,3,4,5
"""

import time

T_START = time.perf_counter()   # set-up is counted from the process's start

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
from fleet import Fleet, metric_samples  # noqa: E402
from sources import Sources  # noqa: E402

PROBE_TOKENS, PROBE_OUT, PROBE_TOL = 200, 16, 0.1
TRACE_SECONDS = 4.0


def emit(**fact):
    print(json.dumps(fact), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------- the cell

class Cell:
    """One ``workloads`` entry resolved to its files, by name only."""

    def __init__(self, manifest_path: str, workload: str):
        self.manifest = load_json(manifest_path)
        entry = [w for w in self.manifest["workloads"]
                 if w["name"] == workload]
        if not entry:
            raise SystemExit(f"no workload {workload!r} in {manifest_path}")
        self.entry = entry[0]
        self.name = workload
        cfg = [c for c in self.manifest["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.mix = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.params = load_json(os.path.join(
            HERE, "cells", workload + ".json"))["params"]
        self.chips = int(self.entry["chips"])
        if self.chips != int(self.config["chips"]):
            raise SystemExit(f"{workload}: the cell asks for {self.chips} "
                             f"chips, its configuration for "
                             f"{self.config['chips']}")

    def reported(self, section: str) -> list:
        """The metrics of ``end_to_end`` / ``per_layer`` this cell reports:
        those without a ``workloads`` key, and those that list it."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]


def reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- small parts

def native_core() -> str:
    """The C++ hashing core is git-ignored, so a fresh checkout lacks it:
    build it once (the fleet's processes load it at import)."""
    so = os.path.join(ROOT, "dynamo_tpu", "libdynamo_native.so")
    if os.path.exists(so):
        return "present"
    r = subprocess.run([sys.executable, "-m", "dynamo_tpu.native_build"],
                       cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH=ROOT))
    return "built" if r.returncode == 0 else "pure python (no g++)"


def probe_body(model: str, vocab: tuple) -> bytes:
    """The fixed probe: the same 200 token ids in every run and seed."""
    ids = loadgen.prompt_ids(PROBE_TOKENS, 0, 20240924, vocab, loadgen.PROBE)
    return loadgen.body_for(model, ids, PROBE_OUT, logprobs=2)


async def probe(fleet: Fleet, body: bytes, label: str) -> loadgen.Stream:
    s = loadgen.Stream(0, "probe", PROBE_OUT, PROBE_TOKENS)
    async with loadgen._session() as session:
        s.start_t = time.perf_counter()
        await loadgen.stream_one(session, fleet.url, body, s,
                                 want_logprobs=True)
    emit(phase="probe", which=label, error=s.error,
         ttft_ms=1000 * s.ttft, chunks=len(s.chunk_t),
         completion_tokens=s.completion_tokens, logprobs=s.logprobs,
         top2_gap=s.top2_gap)
    if s.failed:
        raise SystemExit(f"probe {label} failed: {s.error} "
                         f"(completion_tokens {s.completion_tokens})")
    return s


def compile_cache_is_cold() -> bool:
    """True in a checkout's first run: the persistent compile cache (where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the program's own
    ``.jax_compile_cache/``) does not exist yet or is empty."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache")
    return not os.path.isdir(path) or not os.listdir(path)


def cache_misses(log: str) -> int:
    """Programs the worker really compiled, as its ``JAX_LOG_COMPILES``
    lines tell: every compilation logs "Finished XLA compilation", one that
    was loaded from the persistent cache also "cache hit"."""
    return (log.count("Finished XLA compilation of")
            - log.count("Persistent compilation cache hit for")) // 2


def warm_up(cell: "Cell", fleet: Fleet, body: bytes, cold: bool, seed: int,
            seconds: float) -> tuple[list, float]:
    """Set-up after the fleet is ready: the fixed probe cold and cached,
    then the configuration's shape warm-up and, where that still had to
    compile (a checkout's first run, or a cache that other programs filled),
    one unmeasured rehearsal of the cell's own traffic — the step loop
    compiles small programs per (token bucket, row count) on first use, and
    only the traffic itself finds them all. The rehearsal sends the same
    sizes at the same instants with token ids of another stream, so the
    window finds none of them in the prefix cache. Returns the two probes
    and the seconds the rehearsal took (0.0 where there was none): the
    harness's own pass, which :func:`setup_seconds` takes out again."""
    probes = [asyncio.run(probe(fleet, body, w)) for w in ("cold", "cached")]
    before = cache_misses(fleet.worker_log())
    spec = cell.config.get("warmup")
    if spec:
        emit(phase="warm_shapes", **asyncio.run(loadgen.warm_shapes(
            fleet.url, fleet.model, tuple(cell.mix["vocab"]), spec,
            resend_after_s=0.0 if cold else 4.0)))
    compiled = cache_misses(fleet.worker_log()) - before
    rehearsal_s = 0.0
    if cold or compiled:
        t0 = time.perf_counter()
        asyncio.run(drive(cell, fleet, seed, seconds, cell.params,
                          stream=loadgen.REHEARSAL))
        time.sleep(2.0)
        rehearsal_s = time.perf_counter() - t0
        emit(phase="rehearsal", compile_cache_was_cold=cold,
             compiled_in_warm_up=compiled, seconds=rehearsal_s)
    return probes, rehearsal_s


def setup_seconds(window_start: float, process_start: float,
                  rehearsal_s: float) -> float:
    """``setup_s``: process start to the window's first instant, less the
    harness's rehearsal where one ran. The rehearsal is the harness's pass,
    not the system's set-up, and whether it runs is decided by what the
    machine's compile cache holds for a handful of small eager programs,
    not by the commit under test."""
    return (window_start - process_start) - rehearsal_s


def compiles_logged(log: str) -> list:
    """(epoch, what, seconds) of every XLA compilation the worker logged
    (``JAX_LOG_COMPILES=1`` in the configuration's ``worker_env``)."""
    out = []
    for m in re.finditer(
            r"^(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) .*?Finished XLA "
            r"compilation of (.+?) in ([\d.]+) sec", log, re.M):
        t = time.mktime(time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S"))
        out.append((t + int(m.group(2)) / 1000.0, m.group(3),
                    float(m.group(4))))
    return out


def probes_agree(probes: list) -> dict:
    """The fixed probe, greedy, three times (cold prefix, cached prefix,
    after the window): the logprob of every one of the ``PROBE_OUT`` picked
    tokens within the tolerance of the first probe's. A position may differ
    by more only at or behind a near-tie, a position at which the first
    probe's own top two were closer than the tolerance: a cold and a cached prefix
    round differently in bf16, so a near-tie can flip the pick, and past a
    flipped pick the streams are different text. ``/v1/completions`` hands
    out decoded text, not ids, and the test tokenizer decodes most ids to
    "", so the text is not compared: the logprobs are the numbers."""
    ref = probes[0]
    tie = next((i for i, g in enumerate(ref.top2_gap) if g < PROBE_TOL),
               PROBE_OUT)
    agreeing, gap = PROBE_OUT, 0.0
    for p in probes[1:]:
        diffs = [abs(a - b) for a, b in zip(ref.logprobs, p.logprobs)]
        first_off = next((i for i, d in enumerate(diffs) if d > PROBE_TOL),
                         len(diffs))
        agreeing = min(agreeing, first_off)
        gap = max([gap] + diffs[:first_off])
    ok = (all(len(p.logprobs) == PROBE_OUT for p in probes)
          and tie > 0 and agreeing >= tie)
    return {"ok": ok, "positions_agreeing": agreeing, "of": PROBE_OUT,
            "first_near_tie": tie, "max_logprob_gap": gap,
            "tolerance": PROBE_TOL}


async def get(session, url: str) -> str:
    async with session.get(url) as r:
        text = await r.text()
        if r.status != 200:
            raise SystemExit(f"GET {url}: {r.status} {text[:200]}")
        return text


class Counters:
    """Both ``/metrics`` pages and the flight cursor, read at the window's
    two edges from inside the generator's loop."""

    def __init__(self, fleet: Fleet, seconds: float, trace_dir: str):
        self.fleet, self.seconds, self.trace_dir = fleet, seconds, trace_dir
        self.trace_task = None

    async def _read(self, steps_query: str) -> dict:
        f = self.fleet
        async with loadgen._session() as s:
            worker, front, steps = await asyncio.gather(
                get(s, f"http://127.0.0.1:{f.sys_port}/metrics"),
                get(s, f.url + "/metrics"),
                get(s, f.url + "/v1/fleet/steps?" + steps_query))
        return {"worker": worker, "frontend": front,
                "steps": json.loads(steps)}

    async def at_start(self) -> dict:
        doc = await self._read("n=1")
        self.since = max((s["seq"] for s in steps_of(doc)), default=0)
        if self.trace_dir:
            self.trace_task = asyncio.ensure_future(self._trace())
        return doc

    async def _trace(self):
        """Have the worker trace the window's last ``TRACE_SECONDS``, up to
        the window's end. It is stopped from :meth:`at_end`, once the
        counters are read: writing the trace out stalls the worker's host
        for tens of seconds, which so falls after the window."""
        span = min(TRACE_SECONDS, self.seconds / 3.0)
        await asyncio.sleep(max(0.0, self.seconds - span))
        self.fleet.tell_worker("trace.request", {"dir": self.trace_dir,
                                                 "seconds": span + 10.0})

    async def at_end(self) -> dict:
        doc = await self._read(f"n=16384&since={self.since}")
        if self.trace_dir:
            self.fleet.tell_worker("trace.stop", {})
        return doc


def steps_of(doc: dict) -> list:
    return [s for w in doc["steps"]["workers"].values()
            for s in w.get("steps", [])]


def slice_against_window(flight: list, on: float, e0: float,
                         e1: float) -> dict:
    """What the traced slice [``on``, window end) held beside the whole
    window, from the flight records: a slice is a few seconds of a fixed
    replay, always the same ones, and the device metrics read from it stand
    for the window only as far as these ratios are near 1."""
    def per_s(steps, span):
        return {"steps_per_s": len(steps) / span,
                "prefill_tokens_per_s": sum(
                    s.get("chunk_tokens", 0) for s in steps) / span,
                "decode_tokens_per_s": sum(
                    s.get("decode_rows", 0) for s in steps) / span}
    inside = [s for s in flight if s.get("t", 0) >= on]
    part, whole = per_s(inside, e1 - on), per_s(flight, e1 - e0)
    return {"slice_s": e1 - on, "slice": part, "window": whole,
            "slice_over_window": {k: part[k] / whole[k] if whole[k] else None
                                  for k in part}}


def inflight(run: loadgen.Run, t: float) -> int:
    return sum(1 for s in run.streams if s.sent_t <= t and not s.error
               and not (s.finished and s.chunk_t and s.chunk_t[-1] <= t))


# ------------------------------------------------------------------ phases

async def drive(cell: Cell, fleet: Fleet, seed: int, seconds: float,
                params: dict, stream: int = loadgen.WINDOW,
                trace_dir: str = ""):
    """One pass of the cell's traffic; traced if ``trace_dir`` is given."""
    counters = Counters(fleet, seconds, trace_dir)
    kw = dict(stream=stream, on_window_start=counters.at_start,
              on_window_end=counters.at_end)
    mix, model = cell.mix, fleet.model
    if mix["loop"] == "open":
        schedule = loadgen.open_schedule(mix, float(params["rate_rps"]),
                                         seconds)
        run = await loadgen.run_open(fleet.url, model, mix, schedule,
                                     seconds, seed, **kw)
    elif mix["loop"] == "closed":
        clients = int(params["clients"])
        pool = loadgen.closed_pool(mix, clients)
        run = await loadgen.run_closed(fleet.url, model, mix, pool, clients,
                                       seconds, seed, **kw)
    else:
        raise SystemExit(f"unknown loop kind {mix['loop']!r}")
    if counters.trace_task is not None:
        await counters.trace_task
    return run


def client_numbers(summary: dict) -> dict:
    """The end-to-end metrics, by their names in the manifest."""
    ttft = summary["ttft_s"]
    return {
        "ttft_mean_ms": 1000.0 * sum(ttft) / max(1, len(ttft)),
        "ttft_p90_ms": 1000.0 * loadgen.percentile(ttft, 90),
        "itl_p99_ms": 1000.0 * loadgen.percentile(summary["gaps_s"], 99),
        "tokens_per_s": summary["tokens_in_window"] / summary["window_s"],
    }


def sweep(cell: Cell, fleet: Fleet, rates: list, seed: int, seconds: float):
    """Successive open-loop windows at fixed rates on one fleet, a drain
    between; one JSON line per rate. A rate "sustains" if the tokens received
    per second are within 5% of the tokens offered (the ramp's lag costs
    about 3.5% at every rate) and the median TTFT of the window's second
    half is at most 1.5 times that of its first (no growing backlog).
    Completions against arrivals and requests in flight at the window's
    middle and end are printed beside it: in a 45 s window both lag or
    swing too much to decide alone."""
    for i, rate in enumerate(rates):
        run = asyncio.run(drive(cell, fleet, seed, seconds,
                                {"rate_rps": rate},
                                stream=loadgen.REHEARSAL + 1 + i))
        summ = loadgen.summarize(run)
        w0, w1 = run.window
        done = sum(1 for s in run.streams if s.finished and not s.failed
                   and w0 <= s.chunk_t[-1] < w1)
        arrived = sum(1 for s in run.streams if w0 <= s.start_t < w1)
        mid, end = inflight(run, (w0 + w1) / 2), inflight(run, w1)
        half = [[s.ttft for s in run.streams if s.phase == "window"
                 and lo <= s.start_t < hi]
                for lo, hi in ((w0, (w0 + w1) / 2), ((w0 + w1) / 2, w1))]
        offered = sum(s.max_tokens for s in run.streams
                      if s.phase == "window") / seconds
        emit(phase="sweep", rate_rps=rate, arrivals=arrived, completions=done,
             completions_per_s=done / seconds, arrivals_per_s=arrived
             / seconds, inflight_mid=mid, inflight_end=end,
             offered_tokens_per_s=offered,
             ttft_p50_halves_ms=[1000 * loadgen.percentile(h, 50)
                                 for h in half],
             sustains=bool(
                 summ["tokens_in_window"] / seconds >= 0.95 * offered
                 and loadgen.percentile(half[1], 50)
                 <= 1.5 * loadgen.percentile(half[0], 50)),
             failed=summ["failed"], late_p95_ms=1000 * loadgen.percentile(
                 summ["late_s"], 95), **client_numbers(summ))
        time.sleep(6.0)   # drain: the client closed what was open


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: run the rate sweep instead")
    cli = ap.parse_args()
    cell = Cell(cli.manifest, cli.workload)
    seconds = cli.seconds or float(cell.manifest["run_seconds"])
    traced = bool(cli.trace)
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise SystemExit("no system under test here: dynamo_tpu/ is missing")
    work = os.path.join(ROOT, "chiprun_out", "chipbench", cell.name)
    trace_dir = os.path.join(work, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    cold = compile_cache_is_cold()
    emit(phase="start", workload=cell.name, seed=cli.seed, seconds=seconds,
         trace=cli.trace, params=cell.params, native_core=native_core(),
         compile_cache_cold=cold,
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))

    fleet = Fleet(cell.config, work)
    body = probe_body(fleet.model, tuple(cell.mix["vocab"]))
    try:
        facts = fleet.start(traced, cell.config.get("ready_timeout_s", 1100))
        emit(phase="ready", **facts)
        probes, rehearsal_s = warm_up(cell, fleet, body, cold, cli.seed,
                                      seconds)
        if cli.sweep:
            sweep(cell, fleet, [float(x) for x in cli.sweep.split(",")],
                  cli.seed, seconds)
            return
        run = asyncio.run(drive(cell, fleet, cli.seed, seconds, cell.params,
                                trace_dir=trace_dir if traced else ""))
        setup_s = setup_seconds(run.window[0], T_START, rehearsal_s)
        time.sleep(1.0)   # the client closed its streams: let them cancel
        probes.append(asyncio.run(probe(fleet, body, "after")))
        # the worker's one control thread writes the trace out first (tens of
        # seconds, more on four chips) and only then reads the memory
        trace_done = fleet.wait_answer("trace.done", 300) if traced else None
        mem = fleet.ask_worker("mem.request", "mem.json", {}, 60)
        log = fleet.worker_log()
    finally:
        fleet.stop()

    # ---- the fleet is down and the chip is free: reduce and judge
    summary = loadgen.summarize(run)
    before, after = run.hooks["start"], run.hooks["end"]
    e0, e1 = run.window_epoch
    flight = [s for s in steps_of(after) if e0 <= s.get("t", 0) < e1]
    src = Sources(client=summary, flight=flight,
                  worker_metrics=(before["worker"], after["worker"]),
                  frontend_metrics=(before["frontend"], after["frontend"]),
                  log=log, facts=facts)
    if traced:
        import trace_reduce

        on, off = trace_done["on_epoch"], trace_done["stop_epoch"]
        src.trace = trace_reduce.reduce_trace(
            trace_dir, facts["device"]["kind"], asked_s=off - on)
        src.trace["asked"] = trace_done
        src.trace["slice"] = slice_against_window(flight, on, e0, e1)
        emit(phase="trace_slice", **src.trace["slice"])
        with open(os.path.join(work, "trace_reduced.json"), "w") as f:
            json.dump(src.trace, f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)

    numbers = client_numbers(summary)
    numbers["setup_s"] = setup_s
    fallback = {k: v for k, v in metric_samples(
        after["worker"], "dynamo_ragged_fallback_total").items() if v}
    reasons = {m.group(1) for k in fallback
               for m in [re.search(r'reason="([^"]*)"', k)] if m}
    hit = src.delta_sum("worker", "dynamo_prefix_hit_tokens_total")
    asked = src.delta_sum("worker", "dynamo_prefix_query_tokens_total")
    compiled = [s.get("compile_sig") for s in flight if s.get("compile_s")]
    logged = [(what, secs) for t, what, secs in compiles_logged(log)
              if e0 <= t < e1]
    expect = cell.config["expect"]
    agree = probes_agree(probes)
    checks = {
        "every_ended_stream_exact": summary["streams_wrong"] == 0,
        "window_had_requests": summary["attempted"] > 0,
        "probe_repeats": agree["ok"],
        "no_compile_in_window": not compiled and not any(
            secs >= 1.0 for _, secs in logged),
        "fallback_reasons_exact": reasons == set(expect["fallback_reasons"])
        and len(reasons) == len(fallback),
        # every prompt is unique: what the window finds in the prefix cache
        # was left there by set-up, and the window then measured other work
        "no_prefix_reuse_in_window": hit is not None and bool(asked)
        and hit <= 0.005 * asked,
        "attention_path_expected": facts["attention"] in expect["attention"],
        "weights_bytes_expected": expect.get("weights_bytes") in (
            None, facts["weights_bytes"]),
        "flight_records_cover_window": len(flight) > 0,
    }
    emit(phase="client", **{k: v for k, v in summary.items()
                            if k not in ("ttft_s", "gaps_s", "late_s")},
         late_p95_ms=1000 * loadgen.percentile(summary["late_s"], 95)
         if summary["late_s"] else None,
         steps_in_window=len(flight), probe=agree, rehearsal_s=rehearsal_s,
         **numbers)
    emit(phase="checks", compiled_in_window=compiled,
         small_compiles_in_window={"count": len(logged), "seconds": sum(
             secs for _, secs in logged), "longest": max(
             logged, key=lambda x: x[1], default=None)},
         ragged_fallback_total=fallback, prefix_hit_tokens=hit,
         prefix_query_tokens=asked, **checks)
    with open(os.path.join(work, f"client_{int(time.time())}_seed{cli.seed}"
                                 f"_t{cli.trace}.json"), "w") as f:
        json.dump({"summary": summary, "numbers": numbers, "facts": facts,
                   "checks": checks}, f)

    layer = {}
    for m in cell.reported("per_layer"):
        v = reader(m["name"]).compute(src)
        if v is not None:
            layer[m["name"]] = {"value": v, "unit": m["unit"]}
    emit(phase="per_layer", **{k: v["value"] for k, v in layer.items()})

    # ---- the device check: the reason this cannot pass on a CPU
    device = dict(facts["device"])
    if device["platform"] != "tpu" or device["count"] != cell.chips:
        raise SystemExit(f"no result: served on {device}, the cell needs "
                         f"{cell.chips} TPU chip(s)")
    peaks = [d.get("peak_bytes_in_use") for d in mem["devices"]]
    if not all(peaks):
        raise SystemExit(f"no memory reading from the worker: {mem}")
    device["memory_peak_bytes"] = max(peaks)

    if traced:
        metrics = layer
        device["busy_s"] = src.trace["busy_s_mean"]
        device["window_s"] = src.trace["window_s"]
    else:
        metrics = {m["name"]: {"value": numbers[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.reported("end_to_end")}
    bad = [k for k, v in metrics.items() if not math.isfinite(v["value"])]
    if bad:
        raise SystemExit(f"no result: {bad} not finite "
                         f"({summary['failed']} of {summary['attempted']} "
                         f"requests failed: {summary['errors']})")
    result = {"correct": all(checks.values()),
              "attempted": summary["attempted"], "failed": summary["failed"],
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = src.trace["breakdown"]
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
