"""Per-worker step flight recorder: what every engine step DID, and why it
was slow, in a bounded ring the whole fleet can be asked about.

Request spans (tracing.py) answer "where did THIS request spend its time";
they cannot say that a stall was a preempt-to-swap storm, a mid-traffic XLA
compile, a budget-starved decode batch, or an empty-step memory bubble —
the *step-level* causes the flagship drive (ROADMAP item 3) has to debug.
This module is that missing layer (ref motivation: the KV-cache-management
survey's per-tier visibility argument, arXiv 2607.02574 §6):

- ``StepRecord`` — one scheduler plan / engine step: durations, decode
  rows, prefill chunks + tokens, padded tokens, compile info, preemption /
  swap deltas, queue depths, KV tier occupancy G1–G4, onboard/restore
  pulls in flight, QoS class mix, and the anomaly ``tags`` computed the
  moment the record lands.
- ``PhaseClock`` — the lap clock of the engine loop's thread: every
  millisecond between two records goes to ONE named phase (``PHASES``), so a
  record also says what its step cost the loop (``period_ms``) and what the
  loop was doing meanwhile (``phases``); the same transitions annotate a
  device trace as ``dynamo.<phase>`` under ``DYN_JAX_PROFILER=1``.
- ``FlightRecorder`` — bounded ring of records + rolling step-time
  baseline; tags are computed inline (no offline pass needed):
  ``slow-step`` (wall > kσ over the rolling baseline), ``compile`` /
  ``compile-steady`` (a fresh jit trace; -steady once past the warmup
  step count), ``preempt-storm`` (rolling preemption burst),
  ``budget-starved`` (ready decode rows left out of the step), and
  ``empty-step`` (work exists but nothing could run — a memory bubble).
- ``serve_flight`` / ``fetch_fleet_steps`` — the ``serve_traces``-style
  control-plane fan-out behind ``GET /v1/fleet/steps``, ``dynctl top``
  and ``dynctl timeline``.

Env knobs (all optional):

- ``DYN_FLIGHT=0``            — disable recording entirely (bench A/B arm)
- ``DYN_FLIGHT_CAPACITY``     — ring size in records (default 4096)
- ``DYN_FLIGHT_SIGMA``        — slow-step threshold in rolling σ (default 4)
- ``DYN_FLIGHT_STEADY_STEPS`` — steps after which a compile counts as
  steady-state (default 64)
- ``DYN_FLIGHT_STORM``        — preemptions within the rolling storm
  window (32 records) that tag a preempt-storm (default 4)
- ``DYN_STEP_JSONL=<path>``   — append every record as one JSON line
  (offline analysis; a broken sink disables itself, like DYN_TRACE_JSONL)
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import json
import logging
import math
import os
import secrets
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Optional

import msgpack

logger = logging.getLogger("dynamo.observability.flight")

#: process-unique recorder-instance id. Spans stamp it (engine.ttft /
#: engine.decode ``flight_instance`` attributes) and summaries carry it, so
#: the attribution join (attribution.py) can match "the worker that served
#: this request" to "that worker's step ring" without knowing lease ids —
#: several workers in one fleet share the recorder NAME ("engine"), never
#: the instance.
_INSTANCE_ID = secrets.token_hex(6)


def flight_instance() -> str:
    """This process's recorder-instance id (stable for the process life)."""
    return _INSTANCE_ID

#: discovery prefix: observability/flight/<lease-hex> → {subject, service}
FLIGHT_PREFIX = "observability/flight/"

# anomaly tag names (docs/observability.md "Flight recorder")
TAG_SLOW = "slow-step"
TAG_COMPILE = "compile"
TAG_COMPILE_STEADY = "compile-steady"
TAG_PREEMPT_STORM = "preempt-storm"
TAG_STARVED = "budget-starved"
TAG_EMPTY = "empty-step"

#: rolling windows (records, not seconds): baseline for slow-step σ and
#: the preemption burst window for preempt-storm
BASELINE_WINDOW = 256
STORM_WINDOW = 32
#: minimum baseline samples before slow-step can fire (σ of 3 samples is
#: noise) and the floor added to the σ threshold so microsecond mock steps
#: don't tag on scheduler jitter
BASELINE_MIN_SAMPLES = 16
SLOW_FLOOR_MS = 0.5


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r", name, raw)
        return default


def _env_int(name: str, default: int) -> int:
    return int(_env_float(name, float(default)))


def flight_enabled() -> bool:
    """Global recording gate (``DYN_FLIGHT=0`` = off; the bench A/B arm)."""
    return os.environ.get("DYN_FLIGHT", "1").lower() not in (
        "0", "false", "off", "no")


@dataclass
class StepRecord:
    """One engine step (or one empty-step bubble). All counts are THIS
    step's work/deltas, not cumulative totals — the ring is a timeline."""

    seq: int = 0            # monotonic step index within this recorder
    t: float = 0.0          # epoch seconds at record time
    kind: str = ""          # ragged|spec|multi|decode_pipe|mock|empty —
    #                         ONE record per plan (the packed ragged launch
    #                         is the only step path; no per-bucket records)
    wall_ms: float = 0.0    # plan+execute wall clock; of a ``decode_pipe``
    #                         record the LATENCY of one step through the
    #                         depth-2 pipe (its dispatch → its commit, the
    #                         next step's build and dispatch included)
    dispatch_ms: float = 0.0  # the period's ``dispatch`` phase: the jitted
    #                           step's call (0 = none in the period)
    #: the serving thread's time since the record before this one landed
    #: (what the step COST the loop), and that time by phase, ``{name: ms}``
    #: with zero entries left out, summing to it (``PhaseClock``); both 0 /
    #: empty and off the wire where the engine runs no clock
    period_ms: float = 0.0
    phases: dict = field(default_factory=dict)
    decode_rows: int = 0
    prefill_chunks: int = 0
    chunk_tokens: int = 0   # real prefill tokens this step
    padded_tokens: int = 0  # dispatched beyond real work (bucket tails)
    compile_s: float = 0.0  # >0: this step traced a NEW jit signature
    compile_sig: str = ""   # the offending signature, printable
    preempt_swap: int = 0
    preempt_recompute: int = 0
    swap_out_blocks: int = 0
    swap_in_blocks: int = 0
    waiting: int = 0
    swapped: int = 0
    running: int = 0
    starved_decode: int = 0  # ready decode rows the step could not carry
    #: admitted sequences with prompt tokens left that the plan gave no
    #: chunk (budget, row cap or memory): the queue inside ``running``
    prefill_blocked: int = 0
    #: rows this step sampled under a structured-decoding constraint
    #: (device FSM or host oracle) — docs/structured.md
    constrained_rows: int = 0
    #: rows of this step with more query tokens than the ragged kernel's
    #: small tile (prompt chunks, long verify rows): its wide tile's work
    wide_tile_rows: int = 0
    #: held-experts layer (engine/model._mlp_moe_held), summed over the
    #: step's expert layers: (token, expert) pairs computed here, held
    #: experts with at least one token (the weight bytes the step read),
    #: and the row tiles the grouped matmuls launched (tiles − experts
    #: touched: the tiles that found their expert's weights resident)
    moe_pairs: int = 0
    moe_experts_touched: int = 0
    moe_tiles: int = 0
    #: the same three, a cache group (layer kind):
    #: [[pairs, touched, tiles], ...]
    moe_by_group: list = field(default_factory=list)
    #: buffer rows the layer's read-back fetched (ops/moe_combine.py: the
    #: copies it started) beside the rows a read-back of every pair of every
    #: padded token would fetch, both counted by the layer on the device
    #: and summed over the step's expert layers
    moe_combine_rows: int = 0
    moe_combine_rows_max: int = 0
    #: pages of window cache groups that lie wholly behind their
    #: sequence's window: what releasing them would free
    dead_window_pages: int = 0
    #: a model with recurrent state (engine/cache.py:KvPages.state): slots
    #: held by running sequences after the step, and the rows whose state
    #: the step moved — prefill chunks (the chunked scan) and decode rows
    #: (one update each); all absent for a model without state layers
    state_slots_used: int = 0
    state_rows_prefill: int = 0
    state_rows_decode: int = 0
    #: which step program moved them, as the update kernel's op names have
    #: it: "d" (decode-only) or "m" (mixed) and the token bucket
    state_program: str = ""
    #: a Mamba-2 model's chunk-holding step: (block, chunk row) pairs the
    #: chunked scan's kernel walked and the blocks x RAGGED_MAX_CHUNKS of
    #: the step's token bucket, summed over the Mamba-2 layers (counted on
    #: the host from the plan)
    ssd_block_rows: int = 0
    ssd_block_rows_max: int = 0
    kv_tiers: dict = field(default_factory=dict)  # {g1..g4: blocks}
    onboard_inflight: int = 0
    restore_inflight: int = 0
    qos_mix: dict = field(default_factory=dict)   # {class: rows this step}
    tags: list = field(default_factory=list)
    #: step↔request linkage (attribution.py): request ids whose decode
    #: rows / prefill chunks this step carried, and the ready decode rows
    #: the token budget left out. Sparse on the wire (absent when empty) —
    #: most deployments never fetch them; the attribution join is what
    #: turns "step 4812 was slow" into "THIS request stalled 3 ms there".
    decode_ids: list = field(default_factory=list)
    prefill_ids: list = field(default_factory=list)
    starved_ids: list = field(default_factory=list)
    #: anomaly-triggered device-trace artifact (observability/profiler.py
    #: AnomalyProfiler): set on the record whose tags armed the capture,
    #: AFTER it landed in the ring (snapshots serialize lazily, so fleet
    #: queries see it; a DYN_STEP_JSONL line written at record time does
    #: not — the path is logged as well)
    profile_path: str = ""

    @property
    def tokens(self) -> int:
        return self.decode_rows + self.chunk_tokens

    def to_dict(self) -> dict:
        d = {
            "seq": self.seq, "t": self.t, "kind": self.kind,
            "wall_ms": round(self.wall_ms, 3),
            "decode_rows": self.decode_rows,
            "prefill_chunks": self.prefill_chunks,
            "chunk_tokens": self.chunk_tokens,
            "padded_tokens": self.padded_tokens,
            "waiting": self.waiting, "swapped": self.swapped,
            "running": self.running,
            "prefill_blocked": self.prefill_blocked,
            "tags": list(self.tags),
        }
        # sparse optional fields: absent-when-zero keeps the wire/JSONL
        # compact at fleet scale (most steps are unremarkable)
        if self.dispatch_ms:
            d["dispatch_ms"] = round(self.dispatch_ms, 3)
        if self.period_ms:
            d["period_ms"] = round(self.period_ms, 3)
            d["phases"] = {k: round(v, 3) for k, v in self.phases.items()}
        if self.compile_s:
            d["compile_s"] = round(self.compile_s, 4)
            d["compile_sig"] = self.compile_sig
        for k in ("preempt_swap", "preempt_recompute", "swap_out_blocks",
                  "swap_in_blocks", "starved_decode", "onboard_inflight",
                  "restore_inflight", "constrained_rows", "wide_tile_rows",
                  "moe_pairs", "moe_experts_touched", "moe_tiles",
                  "moe_combine_rows", "moe_combine_rows_max",
                  "dead_window_pages",
                  "state_slots_used", "state_rows_prefill",
                  "state_rows_decode", "state_program", "ssd_block_rows",
                  "ssd_block_rows_max", "profile_path"):
            v = getattr(self, k)
            if v:
                d[k] = v
        for k in ("decode_ids", "prefill_ids", "starved_ids"):
            v = getattr(self, k)
            if v:
                d[k] = list(v)
        if self.moe_pairs:
            d["moe_by_group"] = [list(g) for g in self.moe_by_group]
        if self.kv_tiers:
            d["kv_tiers"] = dict(self.kv_tiers)
        if self.qos_mix:
            d["qos_mix"] = dict(self.qos_mix)
        return d

    @staticmethod
    def from_dict(d: dict) -> "StepRecord":
        rec = StepRecord()
        for k, v in d.items():
            if hasattr(rec, k) and k != "tokens":
                setattr(rec, k, v)
        rec.tags = list(d.get("tags") or [])
        rec.kv_tiers = dict(d.get("kv_tiers") or {})
        rec.qos_mix = dict(d.get("qos_mix") or {})
        rec.phases = dict(d.get("phases") or {})
        return rec


#: what the engine loop's thread can be doing (docs/observability.md "The
#: phase clock" says what each covers); ``other`` is what no mark covers
PHASES = ("idle", "plan", "blocked", "build", "put", "dispatch", "sample",
          "device_wait", "lag", "commit", "record", "other")


class PhaseClock:
    """Lap clock of ONE thread, the engine loop's. At any instant the thread
    is in exactly one phase; ``mark`` enters a phase and thereby ends the
    one before (one ``perf_counter()`` and one add: no nesting, so the
    phases of an interval sum to it by construction). ``cut`` hands over
    what has accumulated since the last cut — the engine cuts at every
    flight record — and the open phase goes on.

    With ``annotate`` the same transitions open and close a
    ``jax.profiler.TraceAnnotation`` named ``dynamo.<phase>``: flat, never
    nested, events of the profiler's host plane on the clock of the
    device's ops, so a device trace says what the host was doing in every
    idle gap. ``landed`` is the one call made from ANOTHER thread (hence
    the lock, taken only when annotating).
    """

    def __init__(self, annotate: bool = False):
        self._acc: dict[str, float] = {}
        self._phase = "other"
        self._t = time.perf_counter()
        self._span = None        # the open TraceAnnotation
        self._span_phase = ""
        self._annotation = None  # the class, when annotating
        self._lock = threading.Lock()
        if annotate:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation
            except Exception:  # jax absent/old: never break serving
                logger.warning("DYN_JAX_PROFILER is set but jax.profiler "
                               "has no TraceAnnotation: not annotating")

    def mark(self, phase: str, at: Optional[float] = None) -> None:
        """Enter ``phase`` now, or at the earlier instant ``at`` (a stamp
        another thread took; never before the open phase began)."""
        now = time.perf_counter() if at is None else max(at, self._t)
        acc = self._acc
        acc[self._phase] = acc.get(self._phase, 0.0) + (now - self._t)
        self._t = now
        self._phase = phase
        if self._annotation is not None:
            self._annotate(phase)

    def landed(self) -> float:
        """Called in a WORKER thread the instant a step's result is on the
        host: the stamp that ends the serving thread's ``device_wait``
        (handed back through the await and given to ``mark`` as ``at``).
        An annotation cannot be opened in the past, so where the serving
        thread is waiting the worker flips it to ``dynamo.lag`` here."""
        now = time.perf_counter()
        if self._annotation is not None and self._phase == "device_wait":
            self._annotate("lag")
        return now

    def _annotate(self, phase: str) -> None:
        with self._lock:
            if phase == self._span_phase:
                return
            if self._span is not None:
                self._span.__exit__(None, None, None)
            self._span = self._annotation("dynamo." + phase)
            self._span.__enter__()
            self._span_phase = phase

    def cut(self) -> tuple[float, dict]:
        """``(period_ms, {phase: ms})`` since the last cut, zero entries
        left out; the period IS the sum of the phases."""
        self.mark(self._phase)
        phases = {k: v * 1000.0 for k, v in self._acc.items() if v > 0.0}
        self._acc = {}
        return sum(phases.values()), phases

    def close(self) -> None:
        """End the open annotation (the loop has stopped)."""
        with self._lock:
            if self._span is not None:
                self._span.__exit__(None, None, None)
            self._span, self._span_phase = None, ""


class FlightRecorder:
    """Bounded step-record ring + inline anomaly tagging.

    Thread-safe: engine loops record from the event loop while scrapes /
    fan-out queries snapshot from other tasks (and the offload thread may
    bump the inflight gauges).
    """

    def __init__(self, service: str = "", capacity: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self.service = service or os.environ.get("DYN_SERVICE", "dynamo")
        self.enabled = flight_enabled() if enabled is None else enabled
        cap = capacity or _env_int("DYN_FLIGHT_CAPACITY", 4096)
        self.sigma = _env_float("DYN_FLIGHT_SIGMA", 4.0)
        self.steady_after = _env_int("DYN_FLIGHT_STEADY_STEPS", 64)
        self.storm_threshold = _env_int("DYN_FLIGHT_STORM", 4)
        self._ring: collections.deque[StepRecord] = collections.deque(
            maxlen=max(16, cap))
        self._lock = threading.Lock()
        self._seq = 0
        #: PER-KIND rolling step-time baselines (non-empty steps) with
        #: running moments — O(1) per record, never a full-window scan.
        #: Per kind, not pooled: a routine 30 ms prefill chunk after a
        #: stretch of ~1 ms pipelined decode steps is NOT a slow step,
        #: and a pooled σ would tag it on every burst boundary.
        self._base: dict[str, list] = {}  # kind -> [deque, sum, sq]
        #: rolling preemption counts for the storm window
        self._storm: collections.deque[int] = collections.deque(
            maxlen=STORM_WINDOW)
        self._storm_sum = 0
        self.anomaly_counts: dict[str, int] = {}
        #: merged [lo, hi] seq intervals snapshots have actually RETURNED
        #: (every slice is seq-contiguous), and the count of records the
        #: ring evicted while never inside any of them — i.e. dropped
        #: before EVER being served. A high-water mark would be wrong
        #: here: an ``n=1`` poll returns only the newest record, and
        #: marking everything older as served would zero the very signal
        #: the attribution join keys its ``incomplete`` flag on
        #: (dynamo_flight_records_dropped_total). The list stays tiny in
        #: practice (pollers repeat/extend one window); a hard cap merges
        #: the closest pair so it can never grow unbounded.
        self._served: list[list[int]] = []
        self.records_dropped_total = 0
        #: external gauges (disagg handler sets onboard/restore inflight;
        #: read at record time so every step carries the current value)
        self.gauges: dict[str, int] = {}
        self._jsonl_path = os.environ.get("DYN_STEP_JSONL") or None

    # ------------------------------------------------------------ recording

    def steady(self) -> bool:
        """Past the warm-up record count — the ONE signal both the
        ``compile-steady`` tag and the engine's steady-state-compile
        WARNING key on, so the tag and the log can never disagree."""
        return self._seq > self.steady_after

    @property
    def seq_now(self) -> int:
        """Latest assigned record seq (0 before any record) — span
        attributes snapshot it to bound a request's step interval."""
        return self._seq

    def set_gauge(self, name: str, value: int) -> None:
        self.gauges[name] = value

    def bump_gauge(self, name: str, delta: int) -> None:
        self.gauges[name] = max(0, self.gauges.get(name, 0) + delta)

    def _baseline(self, kind: str) -> tuple[int, float, float]:
        b = self._base.get(kind)
        if b is None:
            return 0, 0.0, 0.0
        dq, s, sq = b
        n = len(dq)
        if n == 0:
            return 0, 0.0, 0.0
        mean = s / n
        var = max(0.0, sq / n - mean * mean)
        return n, mean, math.sqrt(var)

    def record(self, kind: str, wall_ms: float, **fields) -> (
            Optional[StepRecord]):
        """Append one step record, computing its anomaly tags inline.
        Returns the record (None when recording is disabled)."""
        if not self.enabled:
            return None
        rec = StepRecord(kind=kind, wall_ms=float(wall_ms), t=time.time(),
                         **fields)
        if self.gauges:
            rec.onboard_inflight = rec.onboard_inflight or self.gauges.get(
                "onboard_inflight", 0)
            rec.restore_inflight = rec.restore_inflight or self.gauges.get(
                "restore_inflight", 0)
        with self._lock:
            self._seq += 1
            rec.seq = self._seq
            # ---- tags (computed BEFORE this record joins the baseline, so
            # an outlier can't raise the very threshold it must cross)
            n, mean, std = self._baseline(kind)
            if (kind != "empty" and n >= BASELINE_MIN_SAMPLES
                    and rec.wall_ms > mean
                    + max(self.sigma * std, SLOW_FLOOR_MS)):
                rec.tags.append(TAG_SLOW)
            if rec.compile_s > 0:
                rec.tags.append(TAG_COMPILE)
                if self.steady():
                    rec.tags.append(TAG_COMPILE_STEADY)
            preempts = rec.preempt_swap + rec.preempt_recompute
            self._storm_sum += preempts
            if len(self._storm) == self._storm.maxlen:
                self._storm_sum -= self._storm[0]
            self._storm.append(preempts)
            if preempts and self._storm_sum >= self.storm_threshold:
                rec.tags.append(TAG_PREEMPT_STORM)
            if rec.starved_decode > 0:
                rec.tags.append(TAG_STARVED)
            if kind == "empty":
                rec.tags.append(TAG_EMPTY)
            for t in rec.tags:
                self.anomaly_counts[t] = self.anomaly_counts.get(t, 0) + 1
            # ---- baseline update (empty bubbles excluded: their duration
            # is a wait, not a step time)
            if kind != "empty":
                b = self._base.get(kind)
                if b is None:
                    b = self._base[kind] = [
                        collections.deque(maxlen=BASELINE_WINDOW), 0.0, 0.0]
                dq = b[0]
                if len(dq) == dq.maxlen:
                    old = dq[0]
                    b[1] -= old
                    b[2] -= old * old
                dq.append(rec.wall_ms)
                b[1] += rec.wall_ms
                b[2] += rec.wall_ms * rec.wall_ms
            if len(self._ring) == self._ring.maxlen:
                evicted = self._ring[0].seq
                # retire intervals wholly below the eviction frontier
                while self._served and self._served[0][1] < evicted:
                    self._served.pop(0)
                if not (self._served
                        and self._served[0][0] <= evicted
                        <= self._served[0][1]):
                    self.records_dropped_total += 1
            self._ring.append(rec)
        path = self._jsonl_path
        if path:
            try:
                with open(path, "a") as f:
                    f.write(json.dumps(rec.to_dict()) + "\n")
            except OSError:
                self._jsonl_path = None  # never retry a broken sink per step
        return rec

    # ------------------------------------------------------------- reading

    def _mark_served(self, lo: int, hi: int) -> None:
        """Fold one returned contiguous seq range into the served-interval
        list (caller holds the lock)."""
        merged = []
        for iv in self._served:
            if iv[1] + 1 < lo or hi + 1 < iv[0]:
                merged.append(iv)
            else:  # overlap/adjacency: absorb
                lo, hi = min(lo, iv[0]), max(hi, iv[1])
        merged.append([lo, hi])
        merged.sort()
        while len(merged) > 64:  # bounded: fuse the closest gap (the
            gaps = [(merged[i + 1][0] - merged[i][1], i)  # undercounted
                    for i in range(len(merged) - 1)]      # drop is tiny)
            _, i = min(gaps)
            merged[i][1] = merged[i + 1][1]
            del merged[i + 1]
        self._served = merged

    def snapshot(self, n: Optional[int] = None,
                 since: int = 0) -> list[dict]:
        """Newest-last list of record dicts (the whole ring by default).

        ``since``: only records with ``seq > since`` — the incremental
        cursor behind ``GET /v1/fleet/steps?since=`` (pollers re-fetch
        only what they have not seen). Only the records actually RETURNED
        count as served for the dropped-before-served accounting — and
        they are marked under the SAME lock hold as the copy, so a
        concurrent record() eviction can never count a record this query
        is in the middle of serving as dropped-unserved."""
        with self._lock:
            recs = list(self._ring)
            if since > 0:
                recs = [r for r in recs if r.seq > since]
            if n is not None and n > 0:
                recs = recs[-n:]
            if recs:
                self._mark_served(recs[0].seq, recs[-1].seq)
        return [r.to_dict() for r in recs]

    def first_seq(self) -> int:
        """Oldest seq still in the ring (0 when empty) — the attribution
        join compares it against a request's step interval to detect a
        ring wrap (``incomplete=true``)."""
        with self._lock:
            return self._ring[0].seq if self._ring else 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def summary(self) -> dict:
        """Aggregate view for ``dynctl top``: step counts, rolling wall
        p50/p95, tok/s over the ring, anomaly counts, latest queue/tier
        state."""
        with self._lock:
            recs = list(self._ring)
            anomalies = dict(self.anomaly_counts)
            total = self._seq
        from dynamo_tpu.observability.stats import quantile

        steps = [r for r in recs if r.kind != "empty"]
        walls = [r.wall_ms for r in steps]
        tok_s = 0.0
        if len(steps) >= 2:
            span = steps[-1].t - steps[0].t
            if span > 0:
                tok_s = sum(r.tokens for r in steps) / span
        last = recs[-1] if recs else StepRecord()
        return {
            "service": self.service,
            "instance": _INSTANCE_ID,
            "enabled": self.enabled,
            "steps_total": total,
            "steps_in_ring": len(steps),
            "first_seq": recs[0].seq if recs else 0,
            "last_seq": last.seq,
            "last_t": last.t,
            "dropped_unserved": self.records_dropped_total,
            "wall_p50_ms": round(quantile(walls, 0.50) or 0.0, 3),
            "wall_p95_ms": round(quantile(walls, 0.95) or 0.0, 3),
            "tok_s": round(tok_s, 1),
            "tokens_in_ring": sum(r.tokens for r in steps),
            "anomalies": anomalies,
            "waiting": last.waiting,
            "swapped": last.swapped,
            "running": last.running,
            "kv_tiers": dict(last.kv_tiers),
            "onboard_inflight": self.gauges.get("onboard_inflight", 0),
            "restore_inflight": self.gauges.get("restore_inflight", 0),
        }

    def kind_summary(self, n: int = 2048) -> dict:
        """The newest ``n`` records by kind: steps / seqs / tokens / total
        and mean wall / padded tokens — what the worker's ``/metrics``
        prints as ``engine_step_*`` (a sliding window; empty when
        recording is off). Marks nothing as served."""
        with self._lock:
            recs = list(itertools.islice(reversed(self._ring), n))
        agg: dict[str, list] = {}
        for r in recs:
            if r.kind == "empty":
                continue
            a = agg.setdefault(r.kind, [0, 0, 0, 0.0, 0])
            a[0] += 1
            a[1] += r.decode_rows + r.prefill_chunks
            a[2] += r.tokens
            a[3] += r.wall_ms
            a[4] += r.padded_tokens
        return {k: {"steps": a[0], "seqs": a[1], "tokens": a[2],
                    "total_ms": round(a[3], 1),
                    "mean_ms": round(a[3] / a[0], 1),
                    "padded_tokens": a[4]}
                for k, a in agg.items()}

    def export_jsonl(self, path: str) -> int:
        """Dump the ring as JSONL; returns the line count."""
        recs = self.snapshot()
        with open(path, "w") as f:
            for d in recs:
                f.write(json.dumps(d) + "\n")
        return len(recs)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._base.clear()
            self._storm.clear()
            self._storm_sum = 0
            self.anomaly_counts = {}


# ------------------------------------------------------- process registry

#: name → WEAK ref to a recorder of THIS process; a process may host
#: several engines (mocker DP ranks), each with its own ring, all served
#: by one endpoint. Weak refs mean an engine discarded WITHOUT close()
#: (constructor failure after registration, bench/test churn) cannot pin
#: a ghost ring for the process lifetime — the owner holds the only
#: strong reference, and dead entries self-prune.
_registry: dict[str, "weakref.ref[FlightRecorder]"] = {}
_registry_lock = threading.Lock()


def register_recorder(name: str, rec: FlightRecorder) -> str:
    """Register under ``name`` (suffixing -2, -3… on collision); returns
    the name actually used."""
    with _registry_lock:
        for k in [k for k, r in _registry.items() if r() is None]:
            del _registry[k]
        base, n, final = name, 1, name
        while final in _registry and _registry[final]() is not rec:
            n += 1
            final = f"{base}-{n}"
        _registry[final] = weakref.ref(rec)
        return final


def unregister_recorder(name: str) -> None:
    with _registry_lock:
        _registry.pop(name, None)


def recorders() -> dict[str, FlightRecorder]:
    with _registry_lock:
        out = {}
        for name, ref in _registry.items():
            rec = ref()
            if rec is not None:
                out[name] = rec
        return out


# --------------------------------------------- control-plane fan-out layer


class FlightServeHandle:
    def __init__(self, runtime, key: str, cancel_serve):
        self._runtime = runtime
        self._key = key
        self._cancel = cancel_serve

    async def stop(self) -> None:
        try:
            self._runtime.drop_registration(self._key)
            await self._runtime.plane.kv_delete(self._key)
        finally:
            if self._cancel:
                await self._cancel()


async def serve_flight(runtime) -> FlightServeHandle:
    """Expose this process's flight recorders to fleet queries.

    Query wire: msgpack ``{"n": <records>, "since": <seq>}`` (n<=0 or
    absent → summaries only; since>0 → only records past that seq —
    the incremental-poll cursor) → ``{"service", "workers": {name:
    {"summary", "steps"}}}``. The discovery key rides the primary lease,
    so a dead worker drops out of the fan-out exactly like its serving
    endpoints (collector.py)."""
    lease = await runtime.primary_lease()
    subject = f"flight-{lease:x}"

    async def on_request(payload: bytes) -> bytes:
        try:
            q = msgpack.unpackb(payload, raw=False) or {}
        except Exception:
            q = {}
        n = int(q.get("n") or 0)
        since = int(q.get("since") or 0)
        workers = {}
        for name, rec in recorders().items():
            entry = {"summary": rec.summary()}
            if n > 0 or since > 0:
                entry["steps"] = rec.snapshot(n if n > 0 else None,
                                              since=since)
            workers[name] = entry
        return msgpack.packb({
            "service": os.environ.get("DYN_SERVICE", "dynamo"),
            "workers": workers,
        })

    cancel = await runtime.plane.serve(subject, on_request)
    key = f"{FLIGHT_PREFIX}{lease:x}"
    value = msgpack.packb(
        {"subject": subject,
         "service": os.environ.get("DYN_SERVICE", "dynamo")})
    await runtime.plane.kv_put(key, value, lease_id=lease)
    runtime.record_registration(key, value)
    logger.debug("flight query endpoint on %s", subject)
    return FlightServeHandle(runtime, key, cancel)


async def ensure_flight_endpoint(runtime) -> FlightServeHandle:
    """Idempotent per-runtime ``serve_flight`` (mirrors
    ensure_trace_endpoint: mocker ranks / engine roles register once)."""
    handle = getattr(runtime, "_flight_serve_handle", None)
    if handle is None:
        handle = await serve_flight(runtime)
        runtime._flight_serve_handle = handle
    return handle


async def fetch_fleet_steps(plane, n: int = 0, timeout: float = 2.0,
                            since: int = 0) -> dict:
    """Fan a step query out to every registered flight endpoint.

    Returns ``{"<lease-hex>/<name>": {"summary", "steps"?}}``. A slow or
    dead worker times out individually and is simply dropped — a partial
    fleet view beats none (same contract as fetch_trace). ``since``
    fetches only records past that seq (one cursor applied to every
    worker; per-worker cursors belong to the poller)."""
    try:
        entries = await plane.kv_get_prefix(FLIGHT_PREFIX)
    except Exception:
        logger.exception("flight discovery failed")
        return {}

    async def one(key: str, value: bytes) -> dict:
        try:
            meta = msgpack.unpackb(value, raw=False)
            raw = await asyncio.wait_for(
                plane.request(meta["subject"],
                              msgpack.packb({"n": n, "since": since}),
                              timeout=timeout),
                timeout + 0.5)
            resp = msgpack.unpackb(raw, raw=False) or {}
            lease_hex = key[len(FLIGHT_PREFIX):]
            return {f"{lease_hex}/{name}": entry
                    for name, entry in (resp.get("workers") or {}).items()}
        except Exception:
            return {}  # that worker is gone/slow; keep the rest

    results = await asyncio.gather(
        *(one(k, v) for k, v in entries.items()))
    merged: dict = {}
    for part in results:
        merged.update(part)
    return merged
