"""``python -m dynamo_tpu.runtime.dynctl`` — control-plane server + ops CLI.

Default (no subcommand): run the control-plane server — a single
self-contained process replacing the reference's etcd + NATS pair for
TPU-VM deployments. Point every other process at it with
``DYN_CONTROL_PLANE=host:port``.

HA: run a second dynctl with ``--standby-of primary:port`` and set
``DYN_CONTROL_PLANE=primary:port,standby:port`` everywhere — the standby
mirrors durable state, promotes itself (fresh epoch) after sustained
primary silence, and fences/demotes the old primary if it comes back
(ref HA role: lib/runtime/src/transports/etcd.rs:35-770 replicated etcd).

Subcommands:

- ``dynctl trace <request-id>`` — stitch the request's spans fetched from
  every registered tracer over the control plane (frontend, workers) and
  print the trace tree; ``--json`` dumps the raw span list. Needs
  ``DYN_CONTROL_PLANE`` pointed at the cluster's hub.
- ``dynctl autoscale`` — live view of the closed-loop SLA autoscaler
  (docs/autoscaling.md): controller decision/SLO state, planner target,
  and the operator's desired/alive/ready/draining counts per service;
  ``--watch`` refreshes, ``--json`` dumps the raw status documents.
- ``dynctl top`` — live fleet table from the step flight recorders
  (docs/observability.md "Flight recorder"): per-worker tok/s, step
  p50/p95, anomaly counts, KV tier occupancy G1–G4, queue depths, plus
  the hub's own event counters; ``--watch`` refreshes, ``--json`` dumps.
- ``dynctl timeline <worker>`` — one worker's recent step strip with
  anomaly tags (``!`` slow, ``C`` compile, ``P`` preempt-storm, ``s``
  budget-starved, ``_`` empty bubble) and the tagged records in full;
  ``--watch`` refreshes incrementally via the ``since`` step cursor.
- ``dynctl kv [--worker] [--diff]`` — the KV index audit view
  (docs/observability.md "KV audit"): per worker, the router's
  advertised block count vs the worker's resident count, phantom /
  missing / dangling divergence with age, last heal, suspicion score and
  stale-advert pull failures; ``--diff`` adds divergent-hash samples.
- ``dynctl fleet`` — the fleet scorecard (docs/observability.md "Fleet
  scorecard"): per-class SLO rollup cross-checked against the frontend's
  own histograms, attribution reconciliation, migration outcomes, audit
  divergence/heals, autoscale decisions and hub saturation, fetched from
  a frontend's ``/v1/fleet/scorecard``; ``--watch`` refreshes, ``--json``
  dumps the raw document.
- ``dynctl why <request-id>`` — the per-request latency attribution tree
  (docs/observability.md "Attribution"): the request's spans joined with
  the serving workers' step records, every millisecond bucketed into a
  named cause (queue wait, KV transfer, compile, compute, preempt stall,
  scheduler bubble, …) plus the unattributed residual, with the tagged
  StepRecords behind each stall.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

from dynamo_tpu.runtime.config import setup_logging
from dynamo_tpu.runtime.control_plane import ControlPlaneServer


async def amain(host: str, port: int, persist: str = None,
                persist_interval: float = 5.0, standby_of: str = None,
                takeover_after: float = 6.0, replicate_interval: float = 1.0):
    server = ControlPlaneServer(host, port, persist_path=persist,
                                persist_interval=persist_interval,
                                standby_of=standby_of,
                                takeover_after=takeover_after,
                                replicate_interval=replicate_interval)
    addr = await server.start()
    print(f"dynctl listening on {addr}"
          + (" (standby)" if server.is_standby else ""), flush=True)

    stop = asyncio.Event()
    try:
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
    except (ImportError, NotImplementedError):
        pass
    try:
        await stop.wait()  # SIGTERM → graceful stop → final state flush
    finally:
        await server.stop()


async def trace_amain(request_id: str, as_json: bool, timeout: float) -> int:
    """Fetch + stitch + print one request's distributed trace."""
    from dynamo_tpu.observability import fetch_trace, get_tracer, stitch
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()
    try:
        spans = {d["span_id"]: d
                 for d in await fetch_trace(runtime.plane, request_id,
                                            timeout=timeout)}
        # a dynctl running inside a serving process (tests) also sees its
        # own buffer; standalone CLI runs contribute nothing here
        for s in get_tracer().spans_for(request_id):
            spans.setdefault(s.span_id, s.to_dict())
        ordered = sorted(spans.values(),
                         key=lambda d: d.get("start") or 0.0)
        if not ordered:
            print(f"no spans recorded for request {request_id!r} "
                  "(is DYN_CONTROL_PLANE set, and did the request run "
                  "recently enough to still be in the span ring buffers?)",
                  file=sys.stderr)
            return 1
        if as_json:
            print(json.dumps(ordered, indent=2))
            return 0
        t0 = min(d.get("start") or 0.0 for d in ordered)
        print(f"trace {ordered[0].get('trace_id')} "
              f"(request {request_id}): {len(ordered)} spans")
        for d in stitch(ordered):
            dur = ((d.get("end") or d.get("start") or 0.0)
                   - (d.get("start") or 0.0))
            off = (d.get("start") or 0.0) - t0
            attrs = " ".join(f"{k}={v}" for k, v in
                             (d.get("attributes") or {}).items())
            mark = "" if d.get("status", "ok") == "ok" else " [ERROR]"
            print(f"  {'  ' * d['depth']}{d['name']:<24s} "
                  f"+{off * 1000:8.1f}ms {dur * 1000:8.1f}ms "
                  f"[{d.get('service', '')}]{mark} {attrs}".rstrip())
        return 0
    finally:
        await runtime.shutdown()


async def autoscale_amain(namespace: str, as_json: bool,
                          watch: float = 0.0) -> int:
    """Render the autoscale loop's live state from its control-plane keys."""
    from dynamo_tpu.autoscale.controller import (
        AUTOSCALE_STATUS_KEY, OPERATOR_STATUS_KEY,
    )
    from dynamo_tpu.planner.virtual_connector import SCALE_KEY
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()

    async def read(key_tpl: str):
        raw = await runtime.plane.kv_get(
            key_tpl.format(namespace=namespace))
        return json.loads(raw) if raw else None

    def fmt_age(ts) -> str:
        import time as _t

        return f"{max(0.0, _t.time() - ts):.0f}s ago" if ts else "never"

    try:
        while True:
            ctl = await read(AUTOSCALE_STATUS_KEY)
            op = await read(OPERATOR_STATUS_KEY)
            target = await read(SCALE_KEY)
            if as_json:
                print(json.dumps({"autoscale": ctl, "operator": op,
                                  "plannerTarget": target}, indent=2))
            else:
                print(f"autoscale status (namespace {namespace!r})")
                if ctl is None and op is None and target is None:
                    print("  nothing published — is the autoscaler/operator "
                          "running against this control plane?")
                if ctl:
                    d = ctl.get("desired") or {}
                    r = ctl.get("ready") or {}
                    last = ctl.get("lastDecision") or {}
                    c = ctl.get("counters") or {}
                    print(f"  controller  updated {fmt_age(ctl.get('ts'))}: "
                          f"desired prefill={d.get('prefill')} "
                          f"decode={d.get('decode')}  ready={r or '-'}  "
                          f"backlog={ctl.get('queueDepth')}  "
                          f"workers={ctl.get('workers')}")
                    print(f"  last decision: {last.get('direction')} "
                          f"({last.get('reason')})  "
                          f"ups={c.get('scaleUps')} downs={c.get('scaleDowns')} "
                          f"deferred={c.get('deferredUnready')} "
                          f"cooldown-held={c.get('heldCooldown')} "
                          f"scrape-failures={c.get('scrapeFailures')}")
                    for cls, b in sorted((ctl.get("slo") or {}).items()):
                        mark = "OK" if b.get("ok") else "BREACH"
                        burn = b.get("burn")
                        burn_s = (f"  burn {burn:.2f}x"
                                  if burn is not None else "")
                        print(f"  slo {cls:<12s} ttft p95 "
                              f"{b.get('ttft_p95_ms')}ms / "
                              f"target {b.get('target_ms')}ms  "
                              f"[{mark}]{burn_s}")
                if target:
                    print(f"  planner key: prefill={target.get('prefill')} "
                          f"decode={target.get('decode')} "
                          f"(rev {target.get('revision')})")
                if op:
                    for name, svc in sorted(
                            (op.get("services") or {}).items()):
                        role = svc.get("plannerRole") or "-"
                        gate = "gated" if svc.get("readinessGated") else "ungated"
                        print(f"  {name:<12s} role={role:<8s} "
                              f"desired={svc.get('desired')} "
                              f"alive={svc.get('alive')} "
                              f"ready={svc.get('ready')} "
                              f"draining={svc.get('draining')} "
                              f"restarts={svc.get('restarts')} [{gate}]")
                    print(f"  drains: {op.get('drainsCompleted', 0)} graceful"
                          f", {op.get('drainsKilled', 0)} killed, "
                          f"{op.get('drainSecondsTotal', 0.0)}s total")
            if not watch:
                return 0 if (ctl or op or target) else 1
            await asyncio.sleep(watch)
            print()
    finally:
        await runtime.shutdown()


async def top_amain(as_json: bool, watch: float = 0.0,
                    timeout: float = 2.0) -> int:
    """Live fleet table from every worker's flight recorder summary."""
    from dynamo_tpu.observability import fetch_fleet_steps
    from dynamo_tpu.observability.scorecard import HubSaturationTracker
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()
    # hub-saturation footer: rpc ops/s between refreshes vs the measured
    # ceiling (same ratio dynamo_hub_saturation_ratio{kind="rpc"} exports)
    sat = HubSaturationTracker()

    def fmt_anoms(anoms: dict) -> str:
        labels = (("slow-step", "slow"), ("compile-steady", "steady"),
                  ("compile", "compile"), ("preempt-storm", "storm"),
                  ("budget-starved", "starved"), ("empty-step", "empty"))
        parts = [f"{short}={anoms[k]}" for k, short in labels
                 if anoms.get(k)]
        return " ".join(parts) or "-"

    try:
        while True:
            workers = await fetch_fleet_steps(runtime.plane, n=0,
                                              timeout=timeout)
            hub = None
            if hasattr(runtime.plane, "hub_stats"):
                try:
                    hub = await runtime.plane.hub_stats()
                except Exception:
                    pass
            if as_json:
                print(json.dumps({"workers": workers, "hub": hub},
                                 indent=2))
            else:
                if not workers:
                    print("no flight recorders registered — are workers "
                          "running against this control plane (and is "
                          "DYN_CONTROL_PLANE set)?")
                else:
                    hdr = (f"{'worker':<28s} {'steps':>7s} {'tok/s':>8s} "
                           f"{'p50ms':>8s} {'p95ms':>8s} "
                           f"{'g1/g2/g3/g4':>15s} {'w/s/r':>8s}  anomalies")
                    print(hdr)
                    for name in sorted(workers):
                        s = workers[name].get("summary") or {}
                        t = s.get("kv_tiers") or {}
                        tiers = "/".join(str(t.get(k, 0))
                                         for k in ("g1", "g2", "g3", "g4"))
                        queues = (f"{s.get('waiting', 0)}/"
                                  f"{s.get('swapped', 0)}/"
                                  f"{s.get('running', 0)}")
                        print(f"{name:<28s} {s.get('steps_total', 0):>7d} "
                              f"{s.get('tok_s', 0.0):>8.1f} "
                              f"{s.get('wall_p50_ms', 0.0):>8.2f} "
                              f"{s.get('wall_p95_ms', 0.0):>8.2f} "
                              f"{tiers:>15s} {queues:>8s}  "
                              f"{fmt_anoms(s.get('anomalies') or {})}")
                if hub:
                    ev = hub.get("events") or {}
                    pub = hub.get("publish_seconds") or {}
                    mean_us = (pub["sum"] / pub["count"] * 1e6
                               if pub.get("count") else 0.0)
                    sat.sample(hub)
                    if not watch and sat.rates().get("rpc") is None:
                        # one-shot run: a rate needs two samples — take a
                        # short second one instead of printing nothing
                        await asyncio.sleep(0.3)
                        try:
                            sat.sample(await runtime.plane.hub_stats())
                        except Exception:
                            pass
                    ratio = sat.ratios().get("rpc")
                    sat_txt = (f"  saturation {ratio * 100:.1f}% of "
                               f"{sat.rpc_ceiling:.0f} rpc/s"
                               if ratio is not None else "")
                    print(f"hub: "
                          + " ".join(f"{k}={v}" for k, v in sorted(ev.items()))
                          + f"  publish mean {mean_us:.0f}us over "
                            f"{pub.get('count', 0)} events" + sat_txt)
                    # KV event-stream health (docs/observability.md "KV
                    # audit"): is the radix's feed intact, truncating, or
                    # forcing resyncs?
                    kv = (hub.get("streams") or {}).get("kv_events")
                    if kv:
                        print(f"kv_events: last seq {kv.get('last_seq', 0)} "
                              f"(retained from {kv.get('first_seq', 1)})  "
                              f"truncated {kv.get('truncated', 0)}  "
                              f"resyncs requested "
                              f"{hub.get('resyncs_requested', 0)}")
            if not watch:
                return 0 if workers else 1
            await asyncio.sleep(watch)
            print()
    finally:
        await runtime.shutdown()


#: timeline strip symbols, highest-priority tag wins per record
_STRIP = (("empty-step", "_"), ("preempt-storm", "P"),
          ("compile-steady", "C"), ("compile", "c"), ("slow-step", "!"),
          ("budget-starved", "s"))


def _print_timeline(name: str, entry: dict) -> None:
    steps = entry.get("steps") or []
    summary = entry.get("summary") or {}
    marks = ""
    if entry.get("restarted"):
        marks += "  [recorder restarted — cursor reset]"
    if entry.get("gap"):
        marks += f"  [{entry['gap']} records skipped — raise -n]"
    print(f"{name}: {len(steps)} recent steps "
          f"(p95 {summary.get('wall_p95_ms', 0.0)}ms, "
          f"anomalies {summary.get('anomalies') or {}}){marks}")
    strip = []
    for rec in steps:
        tags = set(rec.get("tags") or [])
        sym = "."
        for tag, ch in _STRIP:
            if tag in tags:
                sym = ch
                break
        strip.append(sym)
    print("  " + "".join(strip))
    for rec in steps:
        if not rec.get("tags"):
            continue
        extras = " ".join(
            f"{k}={rec[k]}" for k in
            ("compile_sig", "compile_s", "preempt_swap",
             "preempt_recompute", "starved_decode", "prefill_blocked",
             "waiting",
             "swapped", "profile_path") if rec.get(k))
        print(f"  #{rec.get('seq'):<7d} {rec.get('kind', ''):<12s} "
              f"{rec.get('wall_ms', 0.0):>9.2f}ms "
              f"dec={rec.get('decode_rows', 0)} "
              f"chunks={rec.get('prefill_chunks', 0)} "
              f"[{','.join(rec.get('tags'))}] {extras}".rstrip())


async def timeline_amain(worker: str, n: int, as_json: bool,
                         timeout: float = 2.0, watch: float = 0.0) -> int:
    """Recent step strip + tagged records for one worker (substring match
    on the fleet key, e.g. ``backend`` or the lease hex). ``--watch``
    polls incrementally: the wire ``since`` carries the LOWEST cursor of
    the matched workers (each recorder's seq counter is independent, so
    one shared high-water mark would freeze the lower-seq workers), and
    the per-worker cursors filter client-side on top."""
    from dynamo_tpu.observability import fetch_fleet_steps
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()
    cursors: dict[str, int] = {}
    first = True
    try:
        while True:
            wire_since = min(cursors.values()) if cursors else 0
            workers = await fetch_fleet_steps(runtime.plane, n=n,
                                              timeout=timeout,
                                              since=wire_since)
            matches = {k: v for k, v in workers.items() if worker in k}
            if not matches and first:
                print(f"no flight recorder matches {worker!r} "
                      f"(known: {sorted(workers) or 'none'})",
                      file=sys.stderr)
                return 1
            first = False
            for key, entry in matches.items():
                cur = cursors.get(key, 0)
                last = int((entry.get("summary") or {}).get("last_seq")
                           or 0)
                if 0 < last < cur:
                    # the worker's recorder restarted (fresh seq counter):
                    # reset this cursor, and the NEXT poll's wire since
                    # (min over cursors) drops low enough to refetch it —
                    # otherwise the server-side filter would hide the new
                    # life's records forever
                    cursors[key] = cur = 0
                    entry["restarted"] = True
                steps = [rec for rec in entry.get("steps") or []
                         if int(rec.get("seq") or 0) > cur]
                entry["steps"] = steps
                if steps:
                    if cur and int(steps[0].get("seq") or 0) > cur + 1:
                        # more new records than -n fetched: mark the hole
                        # instead of rendering a silently-continuous strip
                        entry["gap"] = int(steps[0]["seq"]) - cur - 1
                    cursors[key] = int(steps[-1].get("seq") or 0)
            if as_json:
                print(json.dumps(matches, indent=2))
            else:
                for name in sorted(matches):
                    _print_timeline(name, matches[name])
            if not watch:
                return 0
            await asyncio.sleep(watch)
            print()
    finally:
        await runtime.shutdown()


async def why_amain(request_id: str, as_json: bool, records: int = 2048,
                    timeout: float = 2.0) -> int:
    """Fetch + join + print one request's latency attribution tree."""
    from dynamo_tpu.observability.attribution import gather_attribution
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()
    try:
        doc = await gather_attribution(request_id, runtime=runtime,
                                       records=records, timeout=timeout)
        if doc is None:
            print(f"no spans or step records mention {request_id!r} "
                  "(is DYN_CONTROL_PLANE set, and is the request still "
                  "inside the span/step ring windows?)", file=sys.stderr)
            return 1
        if as_json:
            print(json.dumps(doc, indent=2))
            return 0
        flags = []
        if not doc.get("trace_sampled", True):
            flags.append("trace sampled out — flight-only decomposition")
        if doc.get("incomplete"):
            flags.append("INCOMPLETE: step ring wrapped over part of the "
                         "request's interval")
        print(f"request {doc['request_id']}  e2e {doc['e2e_ms']:.1f}ms  "
              f"qos={doc.get('qos')}  workers={doc.get('workers')}")
        for f in flags:
            print(f"  ! {f}")
        for phase in ("ttft", "itl"):
            total = doc.get(f"{phase}_ms") or 0.0
            buckets = doc.get(phase) or {}
            if not buckets and not total:
                continue
            print(f"  {phase} {total:.1f}ms")
            for bucket, ms in sorted(buckets.items(),
                                     key=lambda kv: -kv[1]):
                if ms <= 0:
                    continue
                pct = 100.0 * ms / total if total else 0.0
                print(f"    {bucket:<16s} {ms:>9.1f}ms {pct:5.1f}%")
                for ev in (doc.get("evidence") or {}).get(bucket, [])[-3:]:
                    bits = " ".join(f"{k}={ev[k]}" for k in
                                    ("kind", "wall_ms", "tags",
                                     "compile_sig", "profile_path")
                                    if ev.get(k))
                    print(f"      · step #{ev.get('seq')} {bits}")
        res = doc.get("residual_ms") or 0.0
        e2e = doc.get("e2e_ms") or 0.0
        print(f"  residual {res:.1f}ms "
              f"({100.0 * res / e2e if e2e else 0.0:.1f}% of e2e)")
        return 0
    finally:
        await runtime.shutdown()


async def kv_amain(worker: str, diff: bool, as_json: bool,
                   watch: float = 0.0, timeout: float = 2.0) -> int:
    """``dynctl kv`` — the KV index audit view (docs/observability.md
    "KV audit"): per worker, the router's advertised block count vs the
    worker's resident count (live digest), divergence classification +
    age, last heal, suspicion, and stale-advert pull failures. The audit
    status comes from the routers' published docs (public/kvaudit/...);
    resident counts are fetched live from each worker's kv_digest
    endpoint so the view works even before any auditor has run."""
    from dynamo_tpu.observability.kvaudit import (fetch_kv_chain,
                                                  fetch_kv_digest,
                                                  list_digest_workers,
                                                  u64_hex)
    from dynamo_tpu.runtime import DistributedRuntime

    runtime = await DistributedRuntime.create()
    try:
        while True:
            statuses = {}
            try:
                for key, value in (await runtime.plane.kv_get_prefix(
                        "public/kvaudit/")).items():
                    try:
                        st = json.loads(value)
                    except Exception:
                        continue
                    # a stopped auditor deletes its doc; a CRASHED one
                    # can't — flag anything older than 3 intervals so a
                    # dead fleet's counts never read as live
                    age = time.time() - float(st.get("ts") or 0)
                    if age > 3 * float(st.get("interval_s") or 30.0):
                        st["stale_s"] = round(age, 1)
                    # key = public/kvaudit/<stream>/<replica>
                    statuses[key[len("public/kvaudit/"):]] = st
            except Exception:
                pass
            endpoints = await list_digest_workers(runtime.plane)
            digests = {}
            for wid in endpoints:
                d = await fetch_kv_digest(runtime.plane, wid, timeout)
                if d is not None:
                    digests[u64_hex(wid)] = d
            if as_json:
                print(json.dumps({"audit": statuses, "digests": digests},
                                 indent=2))
            else:
                # one row per worker: audit status merged with the live
                # digest (live wins for "resident now")
                rows: dict[str, dict] = {}
                for stream, st in statuses.items():
                    if st.get("stale_s"):
                        print(f"warning: audit status for stream "
                              f"{stream!r} is {st['stale_s']}s old "
                              f"(auditor crashed?) — counts below may "
                              f"describe a dead fleet")
                    for whex, w in (st.get("workers") or {}).items():
                        rows[whex] = dict(w)
                for whex, d in digests.items():
                    rows.setdefault(whex, {})["resident_now"] = (
                        d.get("servable") or {}).get("count")
                    rows[whex]["tiers"] = {
                        t: v.get("count", 0)
                        for t, v in (d.get("tiers") or {}).items()}
                shown = {k: v for k, v in rows.items()
                         if not worker or worker in k}
                if not shown:
                    print("no kv_digest endpoints or audit status found — "
                          "are workers (and a kv-mode router) running "
                          "against this control plane?")
                else:
                    print(f"{'worker':<18s} {'advert':>7s} {'resident':>9s} "
                          f"{'phantom':>8s} {'missing':>8s} {'dangling':>9s} "
                          f"{'div-age':>8s} {'heal':>9s} {'susp':>5s} "
                          f"{'stale':>6s}  tiers g1/g2/g3/g4")
                    for whex in sorted(shown):
                        w = shown[whex]
                        res = w.get("resident_now",
                                    w.get("resident_blocks"))
                        t = w.get("tiers") or {}
                        tiers = "/".join(str(t.get(k, 0)) for k in
                                         ("g1", "g2", "g3", "g4"))
                        heal = w.get("last_heal_s_ago")
                        print(f"{whex:<18s} "
                              f"{w.get('advertised_blocks', 0):>7} "
                              f"{res if res is not None else '-':>9} "
                              f"{w.get('phantom', 0):>8} "
                              f"{w.get('missing', 0):>8} "
                              f"{w.get('dangling', 0):>9} "
                              f"{w.get('divergence_age_s', 0.0):>7.1f}s "
                              f"{(f'{heal:.0f}s ago' if heal is not None else 'never'):>9s} "
                              f"{w.get('suspicion', 0):>5} "
                              f"{w.get('stale_adverts', 0):>6}  {tiers}")
                        if diff and w.get("samples"):
                            for kind, hs in sorted(w["samples"].items()):
                                if hs:
                                    print(f"    {kind}: "
                                          + " ".join(f"{h:x}" for h in hs))
                if diff and worker:
                    # live chain fetch for the named worker: the full
                    # resident/anchored view, not just the last audit's
                    # samples
                    for wid in endpoints:
                        whex = u64_hex(wid)
                        if worker not in whex:
                            continue
                        ch = await fetch_kv_chain(runtime.plane, wid,
                                                  timeout)
                        if ch:
                            print(f"  {whex} live chain: "
                                  f"{ch.get('resident_total', 0)} resident, "
                                  f"{len(ch.get('anchored') or ())} "
                                  f"root-anchored")
            if not watch:
                return 0 if (statuses or digests) else 1
            await asyncio.sleep(watch)
            print()
    finally:
        await runtime.shutdown()


def _kv_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl kv",
        description="KV index audit view: advertised vs resident blocks, "
                    "divergence, heals, suspicion per worker")
    ap.add_argument("--worker", default="",
                    help="filter by worker lease-hex substring")
    ap.add_argument("--diff", action="store_true",
                    help="show divergent-hash samples (and, with "
                         "--worker, the live chain summary)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    ap.add_argument("--timeout", type=float, default=2.0)
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        kv_amain(args.worker, args.diff, args.json, args.watch,
                 args.timeout)))


def _top_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl top",
        description="live fleet table from the step flight recorders")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    ap.add_argument("--timeout", type=float, default=2.0,
                    help="per-worker fetch timeout (seconds)")
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        top_amain(args.json, args.watch, args.timeout)))


def _timeline_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl timeline",
        description="recent step strip + tagged records for one worker")
    ap.add_argument("worker", help="fleet key substring "
                                   "(component name or lease hex)")
    ap.add_argument("-n", type=int, default=120,
                    help="recent records to fetch (default 120)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--timeout", type=float, default=2.0)
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds via the since cursor "
                         "(0 = one-shot)")
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        timeline_amain(args.worker, args.n, args.json, args.timeout,
                       args.watch)))


def _why_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl why",
        description="per-request latency attribution: spans joined with "
                    "the serving workers' step records")
    ap.add_argument("request_id")
    ap.add_argument("--records", type=int, default=2048,
                    help="step records to fetch per worker (default 2048)")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw attribution document")
    ap.add_argument("--timeout", type=float, default=2.0)
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        why_amain(args.request_id, args.json, args.records, args.timeout)))


async def fleet_amain(url: str, as_json: bool, watch: float = 0.0,
                      timeout: float = 5.0) -> int:
    """The fleet scorecard (docs/observability.md "Fleet scorecard"):
    GET /v1/fleet/scorecard off a frontend and render the joined
    per-class SLO / attribution / migration / audit / autoscale / hub
    rollup with its falsifiability checks."""
    import aiohttp

    from dynamo_tpu.observability.scorecard import render_scorecard

    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=timeout)) as session:
        while True:
            try:
                async with session.get(
                        f"{url.rstrip('/')}/v1/fleet/scorecard") as resp:
                    doc = await resp.json()
            except Exception as e:
                print(f"scorecard fetch failed: {e}", file=sys.stderr)
                return 1
            if as_json:
                print(json.dumps(doc, indent=2))
            else:
                print(render_scorecard(doc))
            if not watch:
                return 0 if doc.get("ok") else 1
            await asyncio.sleep(watch)
            print()


def _fleet_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl fleet",
        description="render a frontend's fleet scorecard "
                    "(/v1/fleet/scorecard)")
    ap.add_argument("--url", default="http://127.0.0.1:8000",
                    help="frontend base URL (default http://127.0.0.1:8000)")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw scorecard document")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        fleet_amain(args.url, args.json, args.watch, args.timeout)))


async def frontends_amain(url: str, as_json: bool, watch: float = 0.0,
                          timeout: float = 5.0) -> int:
    """Front-door census (docs/robustness.md "Front door"): GET
    /v1/fleet/frontends off any one replica and list every live frontend
    lease with drain-aware readiness. Exit 0 only when at least one
    replica is ready."""
    import aiohttp

    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=timeout)) as session:
        while True:
            try:
                async with session.get(
                        f"{url.rstrip('/')}/v1/fleet/frontends") as resp:
                    doc = await resp.json()
            except Exception as e:
                print(f"frontend census fetch failed: {e}", file=sys.stderr)
                return 1
            if as_json:
                print(json.dumps(doc, indent=2))
            else:
                rows = doc.get("frontends") or []
                print(f"{'replica':<18s}{'url':<32s}{'pid':>8s}"
                      f"{'up_s':>8s}  state")
                now = time.time()
                for fe in rows:
                    up = now - fe["started"] if fe.get("started") else 0.0
                    state = "ready" if fe.get("ready", True) else "draining"
                    if fe.get("self"):
                        state += " *"
                    print(f"{str(fe.get('replica')):<18s}"
                          f"{str(fe.get('url')):<32s}"
                          f"{str(fe.get('pid') or '-'):>8s}"
                          f"{up:>8.1f}  {state}")
                print(f"{doc.get('ready', 0)}/{doc.get('count', 0)} ready")
            if not watch:
                return 0 if doc.get("ready") else 1
            await asyncio.sleep(watch)
            print()


def _frontends_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl frontends",
        description="list live frontend replicas with readiness "
                    "(/v1/fleet/frontends)")
    ap.add_argument("--url", default="http://127.0.0.1:8000",
                    help="any frontend base URL "
                         "(default http://127.0.0.1:8000)")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw census document")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        frontends_amain(args.url, args.json, args.watch, args.timeout)))


async def sessions_amain(url: str, as_json: bool, watch: float = 0.0,
                         timeout: float = 5.0) -> int:
    """Live session registry view (docs/sessions.md): GET /v1/sessions off
    a frontend and render id / turns / affinity worker / idle / parked
    state. Exit 0 when the registry is enabled (even if empty)."""
    import aiohttp

    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=timeout)) as session:
        while True:
            try:
                async with session.get(
                        f"{url.rstrip('/')}/v1/sessions") as resp:
                    doc = await resp.json()
            except Exception as e:
                print(f"session registry fetch failed: {e}", file=sys.stderr)
                return 1
            if as_json:
                print(json.dumps(doc, indent=2))
            else:
                rows = doc.get("sessions") or []
                print(f"{'session':<26s}{'model':<16s}{'turns':>6s}"
                      f"{'worker':>18s}{'idle_s':>8s}{'parked':>8s}"
                      f"{'restored':>9s}  state")
                for s in rows:
                    state = ("active" if s.get("active")
                             else "parked" if s.get("parked") else "idle")
                    print(f"{str(s.get('id'))[:25]:<26s}"
                          f"{str(s.get('model'))[:15]:<16s}"
                          f"{s.get('turns', 0):>6d}"
                          f"{str(s.get('worker') or '-'):>18s}"
                          f"{s.get('idle_s', 0.0):>8.1f}"
                          f"{s.get('parked_blocks', 0):>8d}"
                          f"{s.get('restored_blocks', 0):>9d}  {state}")
                print(f"{doc.get('count', 0)}/{doc.get('cap', '-')} sessions"
                      f" (ttl {doc.get('ttl_s', '-')}s, park after "
                      f"{doc.get('park_after_s', '-')}s)"
                      + ("" if doc.get("enabled", True)
                         else " — registry DISABLED"))
            if not watch:
                return 0 if doc.get("enabled", True) else 1
            await asyncio.sleep(watch)
            print()


def _sessions_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl sessions",
        description="show a frontend's live session registry "
                    "(/v1/sessions: turns, affinity, parked KV)")
    ap.add_argument("--url", default="http://127.0.0.1:8000",
                    help="frontend base URL (default http://127.0.0.1:8000)")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw registry snapshot")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        sessions_amain(args.url, args.json, args.watch, args.timeout)))


def _autoscale_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl autoscale",
        description="show the closed-loop SLA autoscaler's live state")
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw status documents")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="refresh every N seconds (0 = one-shot)")
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        autoscale_amain(args.namespace, args.json, args.watch)))


def _trace_main(argv: list[str]) -> None:
    ap = argparse.ArgumentParser(
        prog="dynctl trace",
        description="stitch and print a request's distributed trace")
    ap.add_argument("request_id")
    ap.add_argument("--json", action="store_true",
                    help="dump raw span dicts instead of the tree view")
    ap.add_argument("--timeout", type=float, default=2.0,
                    help="per-tracer fetch timeout (seconds)")
    args = ap.parse_args(argv)
    raise SystemExit(asyncio.run(
        trace_amain(args.request_id, args.json, args.timeout)))


def main():
    setup_logging()
    if len(sys.argv) > 1 and sys.argv[1] == "trace":
        _trace_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "autoscale":
        _autoscale_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "top":
        _top_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "timeline":
        _timeline_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "why":
        _why_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "kv":
        _kv_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        _fleet_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "frontends":
        _frontends_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "sessions":
        _sessions_main(sys.argv[2:])
        return
    ap = argparse.ArgumentParser(description="dynamo-tpu control plane server")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=6650)
    ap.add_argument("--persist", default=None, metavar="FILE",
                    help="durable-state file: discovery keys, object store "
                         "and stream tails survive a restart (leases do "
                         "not); snapshotted every --persist-interval s, "
                         "flushed on SIGTERM")
    ap.add_argument("--persist-interval", type=float, default=5.0)
    ap.add_argument("--standby-of", default=None, metavar="HOST:PORT",
                    help="run as a warm standby of this primary: mirror its "
                         "durable state, reject client ops, and promote to "
                         "primary (fresh epoch) after --takeover-after s of "
                         "primary silence; point clients at "
                         "DYN_CONTROL_PLANE=primary,standby")
    ap.add_argument("--takeover-after", type=float, default=6.0)
    ap.add_argument("--replicate-interval", type=float, default=1.0)
    args = ap.parse_args()
    asyncio.run(amain(args.host, args.port, args.persist,
                      args.persist_interval, args.standby_of,
                      args.takeover_after, args.replicate_interval))


if __name__ == "__main__":
    main()
