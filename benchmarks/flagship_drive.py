"""Flagship fleet drive: the 70B-on-v5e-64 placement, everything on at once.

ROADMAP item 2's closing proof (ISSUE 16): instead of per-subsystem
tiny-cpu benches, ONE multihost-sim run instantiates the
``benchmarks/plan_70b.py`` placement — 2×TP8 prefill + 6×TP8 decode on a
v5e-64 — as a mocker fleet spawned by the process operator, with
DCN-class topology labels (prefill and decode pools on different slices
of one pod) and PLAN-derived step timings (``--decode-base-ms`` etc. from
the solved 17 ms roofline step), and drives one diurnal QoS-mixed cycle
through it with every plane live simultaneously:

- KV routing + the event-fed radix index (+ its auditor at a 2 s cadence
  so divergence from kills heals *within* the run),
- the autoscale controller + operator closed loop (scale up at the peak,
  back down overnight),
- seeded chaos ``worker.kill`` on the decode pool: ≥2 mid-decode deaths
  the fleet must absorb with ZERO lost tokens (migration + restarts),
- the frontend's attribution sampler (``DYN_ATTR_FEED_S``) feeding the
  scorecard's per-request reconciliation,
- the fleet scorecard (``observability/scorecard.py``) marking the
  diurnal phases and cross-checking every rollup against the frontend's
  own histograms,
- ``dynamo_hub_saturation_ratio{kind}`` live on /metrics, measured
  against the ceilings in docs/PERF_NOTES.md.

The drive is falsifiable end to end: it FAILS unless completion is 100%
with zero lost tokens, the autoscaler scaled up AND down, audit
divergence healed to zero with at least one heal, every scorecard check
passed, and the saturation gauge carried live rates.

Run standalone::

    python -m benchmarks.flagship_drive [--duration 40] [--scale 1.0] \
        [--json out.json]

The tier-1
smoke (tests/test_scorecard.py) runs a scaled-down bounded cycle.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import time
from typing import Optional

#: diurnal phase boundaries as fractions of the traffic window — each one
#: closes a scorecard phase card with its own falsifiability checks
PHASES = (("morning-ramp", 0.35), ("peak", 0.65), ("evening", 1.0))


def plan_timing_args(solved: dict) -> list[str]:
    """Mocker step-timing flags derived from the plan's solved roofline.

    The solved decode step (17 ms at the 217-seq max batch for
    tp8_wint4_kvint8) splits into a fixed dispatch cost and a per-sequence
    cost; prefill tokens cost the roofline-rate per token. The mocker then
    exhibits the PLAN's step economics instead of the generic tiny-model
    defaults."""
    step_ms = float(solved["step_ms_roofline"])
    max_batch = int(solved["max_batch_per_worker"])
    tok_s_worker = float(solved["tok_s_per_chip_roofline"]) * int(solved["tp"])
    return [
        "--decode-base-ms", f"{0.2 * step_ms:.4f}",
        "--decode-per-seq-ms", f"{0.8 * step_ms / max_batch:.5f}",
        "--prefill-base-ms", f"{step_ms:.4f}",
        "--prefill-per-token-ms", f"{1000.0 / tok_s_worker:.5f}",
    ]


async def drive(duration_s: float = 40.0, scale: float = 1.0,
                seed: int = 1234, kill_error: float = 0.0015,
                autoscale: bool = True) -> dict:
    """One full diurnal cycle at the (possibly scaled) 70B placement.

    ``scale`` shrinks the fleet for bounded smokes (0.5 → 1 prefill +
    3 decode); 1.0 is the flagship 2+6 placement. ``autoscale=False``
    pins the fleet (smoke mode: no controller, shorter run)."""
    import sys
    import tempfile

    import aiohttp
    import numpy as np
    import yaml

    from benchmarks.client import Mix, make_prompt, qos_headers, stream_request
    from benchmarks.plan_70b import placement
    from dynamo_tpu.deploy.operator import ProcessOperator
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.control_plane import ControlPlaneServer

    plan = placement()
    MODEL = "llama3-70b-sim"
    OSL, ISL_WORDS = 24, 48
    n_prefill = max(1, round(plan["prefill"]["workers"] * scale))
    n_decode = max(2, round(plan["decode"]["workers"] * scale))
    min_decode = max(1, n_decode - 2)
    max_decode = n_decode + 2
    # traffic sine sized so the planner's claimed ~2 req/s per replica
    # demands more than n_decode at the peak and fewer at the trough
    base_rps = 0.9 * n_decode
    amp_rps = 0.8 * base_rps
    period = duration_s
    INT_TTFT_SLO_MS = 1500.0

    server = ControlPlaneServer(port=0)
    addr = await server.start()
    env_overrides = {
        "DYN_CONTROL_PLANE": addr,
        # audit cadence fast enough that kill-induced divergence heals
        # INSIDE the run (default 30 s would outlive the whole cycle)
        "DYN_KV_AUDIT_INTERVAL": "2",
        "DYN_KV_AUDIT_SETTLE": "0.1",
        # continuous attribution sampling feeds the scorecard's
        # per-request e2e reconciliation
        "DYN_ATTR_FEED_S": "0.5",
        # frontend + controller read the SAME SLO spec from env
        "DYN_SLO_INTERACTIVE_TTFT_P95_MS": str(INT_TTFT_SLO_MS),
        "DYN_SLO_INTERACTIVE_ITL_MS": "80",
        "DYN_SLO_STANDARD_TTFT_P95_MS": "6000",
        "DYN_SLO_STANDARD_ITL_MS": "120",
        "DYN_SLO_MIN_REPLICAS": str(min_decode),
        "DYN_SLO_MAX_REPLICAS": str(max_decode),
        "DYN_SLO_COOLDOWN_UP_S": "2",
        "DYN_SLO_COOLDOWN_DOWN_S": "6",
        "DYN_SLO_INTERVAL_S": "1",
        "DYN_SLO_PREDICTOR": "arima",
        "DYN_SLO_BACKLOG_PER_REPLICA": "3",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)

    tmp = tempfile.mkdtemp(prefix="flagship-drive-")
    spec_path = os.path.join(tmp, "graph.yaml")
    timing = plan_timing_args(plan["decode"])

    def worker_cmd(component: str) -> list[str]:
        return [
            sys.executable, "-m", "dynamo_tpu.mocker.main",
            "--model", MODEL, "--component", component,
            "--block-size", "16", "--num-gpu-blocks", "4096",
            "--max-num-seqs", "8",
            # wall-clock compression: plan step economics, sim'd faster
            # than real time so one diurnal cycle fits a bench budget
            "--speedup-ratio", "4.0",
            "--migration-limit", "50",
            *timing,
        ]

    common_env = {
        "DYN_CONTROL_PLANE": addr,
        "PYTHONPATH": os.pathsep.join(sys.path),
        "JAX_PLATFORMS": "cpu",
        "DYN_DRAIN_TIMEOUT": "8",
        "DYN_LOG": "warning",
        "DYN_TOPO_POD": "pod0",
    }
    services = {
        "prefill": {
            "replicas": n_prefill, "plannerRole": "prefill",
            "command": worker_cmd("prefill"),
            "env": {**common_env, "DYN_TOPO_SLICE": "v5e-64-pf",
                    "DYN_TOPO_HOST": "host-pf"},
        },
        "decode": {
            "replicas": n_decode, "plannerRole": "decode",
            "command": worker_cmd("decode"),
            # seeded mid-decode kills live in the DECODE pool: that is
            # where in-flight streams break and migration must absorb
            # ...plus seeded KV-event loss: dropped stored-block publishes
            # are invisible to the router's gap detection (lost BEFORE the
            # hub assigns a seq), so only the auditor's resync heals the
            # resulting divergence — the drive exercises that plane too
            "env": {**common_env, "DYN_TOPO_SLICE": "v5e-64-dec",
                    "DYN_TOPO_HOST": "host-dec",
                    "DYN_CHAOS": (f"worker.kill:error={kill_error};"
                                  "plane.publish:drop=0.02"),
                    "DYN_CHAOS_SEED": str(seed)},
        },
    }
    with open(spec_path, "w") as f:
        yaml.safe_dump({
            "apiVersion": "dynamo.tpu/v1alpha1",
            "kind": "DynamoGraphDeployment",
            "metadata": {"name": "flagship-drive"},
            "spec": {"services": services},
        }, f)

    rt = await DistributedRuntime.create()
    manager = ModelManager()
    watcher = service = operator = aggregator = runner = None
    controller = None
    results: list = []
    by_class: dict = {}
    metrics_scrapes = 0
    saturation_seen = False
    last_metrics_text = ""
    try:
        watcher = await ModelWatcher(rt, manager, router_mode="kv").start()
        service = HttpService(manager, port=0, runtime=rt)
        await service.start()
        operator = await ProcessOperator(
            spec_path, plane=rt.plane, tick_s=0.25, drain_timeout=10.0
        ).start()
        frontend_url = f"http://127.0.0.1:{service.port}"

        if autoscale:
            from dynamo_tpu.autoscale import (
                AutoscaleController, AutoscaleRunner, ObservationFuser,
                SloConfig, make_planner, plane_readiness,
            )
            from dynamo_tpu.planner.perf_interpolation import PerfInterpolator
            from dynamo_tpu.planner.prometheus import PrometheusMetricsSource
            from dynamo_tpu.planner.virtual_connector import VirtualConnector
            from dynamo_tpu.router.publisher import MetricsAggregator

            slo = SloConfig.load()
            # planner sweep claiming ~36 decode tok/s per replica at the
            # 80 ms ITL target (≈1.5 req/s at OSL 24): the sine's peak
            # (~9.7 req/s → 7 replicas) then demands well above the
            # min_decode floor and the overnight trough falls back to it.
            # no_correction: the mocker's wall-clock-compressed ITL would
            # otherwise feed the adaptive correction an absurdly fast
            # observation and inflate per-replica capacity past the sweep
            prefill_perf = PerfInterpolator([(1.0, 200.0), (2.0, 700.0),
                                             (4.0, 2500.0)])
            decode_perf = PerfInterpolator([(24.0, 20.0), (36.0, 80.0),
                                            (72.0, 400.0)])
            aggregator = await MetricsAggregator(
                rt.plane, stale_after_s=3.0).start()
            fuser = ObservationFuser(
                PrometheusMetricsSource(frontend_url), aggregator)
            planner = make_planner(slo, prefill_perf, decode_perf,
                                   min_prefill_replicas=n_prefill,
                                   max_prefill_replicas=n_prefill,
                                   no_correction=True)

            async def readiness():
                return await plane_readiness(rt.plane, "dynamo")

            controller = AutoscaleController(
                slo, planner, fuser, VirtualConnector(rt.plane),
                readiness=readiness, metrics=rt.metrics, plane=rt.plane)
            runner = await AutoscaleRunner(controller).start()

        for _ in range(300):  # fleet registered + model discovered
            if manager.list_models():
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("mocker fleet never appeared in discovery")

        mix = Mix("interactive=0.5,standard=0.3,batch=0.2")
        rng = np.random.default_rng(seed)
        import random as _random

        prompt_rng = _random.Random(seed)
        inflight: set = set()
        phantom_injected = False

        def _inject_phantom() -> bool:
            """Plant the canonical INVISIBLE loss shape directly: stored
            adverts in the radix for blocks no worker holds (exactly what
            a removal event dropped before the hub assigned it a seq
            leaves behind). Gap detection can never see it — only the
            auditor's digest sweep — so injecting one mid-drive makes the
            heal gate deterministic instead of riding on the chaos drop
            happening to hit a KV event this particular run."""
            from dynamo_tpu.router.protocols import (
                KvCacheEvent, RouterEvent, StoredBlock,
            )
            sm = manager.get(MODEL)
            router = getattr(sm, "router", None) if sm else None
            indexer = getattr(router, "indexer", None)
            tree = getattr(indexer, "tree", None)
            if tree is None:
                return False
            live = [w for w, c in tree.worker_counts().items()
                    if w >= 0 and c > 0]
            if not live:
                return False
            blocks = [StoredBlock(block_hash=0x7E57_0000 + i,
                                  tokens_hash=0x7E57_1000 + i)
                      for i in range(6)]
            tree.apply_event(RouterEvent(
                live[0], KvCacheEvent.stored(0, None, blocks)))
            return True

        await service.scorecard.mark_phase(PHASES[0][0])
        phase_idx = 0
        t0 = time.monotonic()
        tail_budget = (3 * 6.0 + 12.0) if autoscale else 4.0
        async with aiohttp.ClientSession() as session:
            while (now := time.monotonic() - t0) < duration_s + tail_budget:
                # advance the diurnal phase markers (scorecard cards)
                while (phase_idx < len(PHASES) - 1
                       and now >= PHASES[phase_idx][1] * duration_s):
                    phase_idx += 1
                    await service.scorecard.mark_phase(PHASES[phase_idx][0])
                if phase_idx >= 2 and not phantom_injected:
                    # post-peak: the fleet is warm and advertising — seed
                    # the divergence the audit plane must detect and heal
                    # before the run's final snapshot
                    phantom_injected = _inject_phantom()
                if now < duration_s:
                    rate = max(0.1, base_rps + amp_rps * math.sin(
                        2 * math.pi * now / period - math.pi / 2))
                else:
                    if phase_idx == len(PHASES) - 1:
                        phase_idx += 1
                        await service.scorecard.mark_phase("overnight")
                    rate = 0.4
                    if (controller is not None
                            and controller.applied.decode_replicas
                            == min_decode
                            and operator._status()["services"]["decode"]
                            ["ready"] == min_decode):
                        break  # settled at the overnight floor
                    if controller is None:
                        break  # pinned fleet: no scale-down to wait for
                cls = mix.pick(prompt_rng)
                task = asyncio.get_running_loop().create_task(
                    stream_request(
                        session, frontend_url, MODEL,
                        make_prompt(prompt_rng, ISL_WORDS), OSL,
                        headers=qos_headers(None, cls)))
                inflight.add(task)

                def _done(t, cls=cls):
                    inflight.discard(t)
                    results.append(t.result())
                    by_class.setdefault(cls, []).append(t.result())

                task.add_done_callback(_done)
                # periodic /metrics scrape: keeps the saturation window
                # fed and proves the gauge is live DURING the drive
                if int(now * 2) > metrics_scrapes:
                    metrics_scrapes = int(now * 2)
                    try:
                        async with session.get(
                                f"{frontend_url}/metrics") as resp:
                            last_metrics_text = await resp.text()
                        if "dynamo_hub_saturation_ratio{" \
                                in last_metrics_text:
                            saturation_seen = True
                    except Exception:
                        pass
                await asyncio.sleep(float(rng.exponential(1.0 / rate)))
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)
            # let the audit plane converge before the final snapshot: the
            # last kills/drops can leave divergence the auditor has
            # DETECTED but not yet resynced (heals land one cadence after
            # detection) — the gate is "healed to zero inside the run",
            # so grant it a few cycles, bounded
            for _ in range(40):
                div = sum(
                    sum((a.get("divergence_blocks") or {}).values())
                    for a in service.scorecard.audit_rollup().values())
                if div == 0:
                    break
                await asyncio.sleep(0.25)
            # close the final scorecard phase and pull the document + one
            # last /metrics scrape while the fleet is still up
            await service.scorecard.mark_phase(None)
            scorecard_doc = await service.scorecard.document()
            async with session.get(f"{frontend_url}/metrics") as resp:
                last_metrics_text = await resp.text()
            if "dynamo_hub_saturation_ratio{" in last_metrics_text:
                saturation_seen = True
        final_status = operator._status()
        hub_stats = await rt.plane.hub_stats() \
            if hasattr(rt.plane, "hub_stats") else {}
    finally:
        if runner is not None:
            await runner.stop()
        if aggregator is not None:
            await aggregator.stop()
        if operator is not None:
            await operator.stop()
        if service is not None:
            await service.stop()
        if watcher is not None:
            await watcher.stop()
        await rt.shutdown()
        await server.stop()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    ok = [r for r in results if r.ok]
    lost_tokens = sum(OSL - r.completion_tokens for r in ok)
    if os.environ.get("DYN_DRIVE_DEBUG"):
        for r in ok:
            if r.completion_tokens != OSL:
                print(f"DRIVE_DEBUG short stream: usage={r.completion_tokens}"
                      f" chunks={r.tokens} err={r.error}", flush=True)
    int_res = by_class.get("interactive", [])
    int_ttfts = sorted(r.ttft_s for r in int_res if r.ttft_s is not None)
    int_p95 = (int_ttfts[max(0, math.ceil(0.95 * len(int_ttfts)) - 1)]
               if int_ttfts else None)
    restarts = sum(s.get("restarts", 0)
                   for s in final_status["services"].values())
    audit_now = scorecard_doc["now"]["audit"]
    divergence_end = sum(sum((a.get("divergence_blocks") or {}).values())
                         for a in audit_now.values())
    heals = sum(sum((a.get("heals_total") or {}).values())
                for a in audit_now.values())
    failed_checks = [c["name"] for c in scorecard_doc["checks"]
                     if not c["ok"]]
    for p in scorecard_doc["phases"]:
        failed_checks += [f"{p['phase']}:{c['name']}"
                          for c in p["checks"] if not c["ok"]]
    hub_now = scorecard_doc["now"]["hub"]
    events = (hub_stats or {}).get("events") or {}
    total_ev = sum(events.values()) or 1
    out = {
        "placement": {
            "combo": plan["combo"], "prefill_workers": n_prefill,
            "decode_workers": f"{min_decode}-{max_decode}",
            "scale": scale,
            "step_ms_roofline": plan["decode"]["step_ms_roofline"],
        },
        "workload": (f"sine {base_rps:.1f}±{amp_rps:.1f} req/s x "
                     f"{duration_s:.0f}s, OSL {OSL}, "
                     f"mix int/std/batch .5/.3/.2, "
                     f"chaos worker.kill:error={kill_error}"),
        "requests": len(results), "ok": len(ok),
        "failed": len(results) - len(ok),
        "lost_tokens": lost_tokens,
        "int_ttft_p95_ms": (round(int_p95 * 1000, 1)
                            if int_p95 is not None else None),
        "worker_restarts": restarts,
        "migrations": scorecard_doc["now"]["migrations"],
        "scale_ups": controller.scale_ups if controller else 0,
        "scale_downs": controller.scale_downs if controller else 0,
        "audit_divergence_end": divergence_end,
        "audit_heals": heals,
        "phantom_injected": phantom_injected,
        "scorecard_phases": len(scorecard_doc["phases"]),
        "scorecard_checks": len(scorecard_doc["checks"]) + sum(
            len(p["checks"]) for p in scorecard_doc["phases"]),
        "scorecard_failed_checks": failed_checks,
        "hub_rpc_per_s": (hub_now.get("rates") or {}).get("rpc"),
        "hub_blocks_per_s": (hub_now.get("rates") or {}).get("blocks"),
        "hub_saturation": hub_now.get("saturation"),
        "hub_event_mix": {k: round(v / total_ev, 4)
                          for k, v in sorted(events.items())},
        "saturation_gauge_live": saturation_seen,
        "scorecard": scorecard_doc,
    }
    gates = [
        out["failed"] == 0,
        lost_tokens == 0,
        divergence_end == 0,
        not failed_checks,
        out["scorecard_phases"] >= (4 if autoscale else 3),
        saturation_seen,
    ]
    if autoscale:
        gates += [
            restarts >= 2,          # ≥2 chaos kills absorbed
            phantom_injected,       # the seeded divergence went in...
            heals > 0,              # ...and the auditor healed it
            out["scale_ups"] >= 1 and out["scale_downs"] >= 1,
        ]
    out["flagship_ok"] = all(gates)
    return out


async def _spawn_frontend(idx: int, env: dict, timeout_s: float = 40.0):
    """Launch ``python -m dynamo_tpu.frontend.main`` as replica ``fe-<idx>``
    and wait for its FRONTEND_READY line. Returns (proc, port, drain_task);
    the drain task keeps consuming stdout so the pipe can never backpressure
    the child."""
    import sys

    debug = bool(os.environ.get("DYN_DRIVE_DEBUG"))
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_tpu.frontend.main",
        "--port", "0", "--replica-id", f"fe-{idx}", "--router-mode", "kv",
        env=env, stdout=asyncio.subprocess.PIPE,
        stderr=(None if debug else asyncio.subprocess.DEVNULL))
    port = None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            line = await asyncio.wait_for(proc.stdout.readline(),
                                          deadline - time.monotonic())
        except asyncio.TimeoutError:
            break
        if not line:
            break
        text = line.decode(errors="replace").strip()
        if text.startswith("FRONTEND_READY"):
            port = int(text.rpartition("=")[2])
            break
    if port is None:
        proc.kill()
        raise RuntimeError(f"frontend fe-{idx} never became ready")

    async def _drain():
        while await proc.stdout.readline():
            pass

    return proc, port, asyncio.get_running_loop().create_task(_drain())


async def frontdoor_drive(duration_s: float = 30.0, seed: int = 1234,
                          n_frontends: int = 3) -> dict:
    """Front-door chaos leg (ISSUE 18, docs/robustness.md "Front door").

    N frontend REPLICA subprocesses share one hub-fed KV routing view over
    a primary+standby hub pair; a mocker fleet serves behind them. The
    client drives QoS-less traffic through ``stream_request_ha`` (all
    replica URLs, bounded retries). Mid-peak one frontend is SIGKILLed;
    shortly after, the hub PRIMARY dies and the standby promotes under
    live load. Falsifiable gates:

    - 100% client completion within the bounded retry budget, with zero
      lost and zero duplicated tokens (usage.completion_tokens == OSL
      exactly, every stream);
    - the surviving replicas' per-worker radix digests agree after settle
      (``/v1/kv/digest``), and each survivor force-resynced on the hub
      epoch change (the in-band epoch marker — no silent seq-continuity
      loss from the promoted standby);
    - zero leaked seqs/blocks on the workers once traffic stops (a worker
      still stepping an orphaned seq keeps publishing fresh metrics —
      idle-stale aggregation is the no-leak signal);
    - the KV auditor and the autoscale loop keep cycling AFTER promotion;
    - the dead replica ages out of the front-door listing while the
      survivors stay ready.
    """
    import sys

    import aiohttp
    import numpy as np
    import yaml

    from benchmarks.client import make_prompt, stream_request_ha
    from dynamo_tpu.deploy.operator import ProcessOperator
    from dynamo_tpu.runtime import DistributedRuntime, RemoteControlPlane
    from dynamo_tpu.runtime.control_plane import ControlPlaneServer

    MODEL = "llama3-ha-sim"
    OSL, ISL_WORDS = 16, 32
    # bounded failover budget: wide enough that a request landing exactly
    # on the frontend-kill + hub-promotion overlap can ride out the
    # reconnect window (attempt backoff spans ~7s), still a hard cap
    MAX_ATTEMPTS = 6
    n_prefill, n_decode = 1, 3

    primary = ControlPlaneServer(port=0)
    p_addr = await primary.start()
    standby = ControlPlaneServer(port=0, standby_of=p_addr,
                                 takeover_after=0.8, replicate_interval=0.1)
    s_addr = await standby.start()
    addrs = f"{p_addr},{s_addr}"

    env_overrides = {
        "DYN_CONTROL_PLANE": addrs,
        "DYN_LEASE_TTL": "2",
        "DYN_KV_AUDIT_INTERVAL": "2",
        "DYN_KV_AUDIT_SETTLE": "0.1",
        "DYN_SLO_MIN_REPLICAS": str(n_decode),
        "DYN_SLO_MAX_REPLICAS": str(n_decode),
        "DYN_SLO_INTERVAL_S": "1",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)

    import tempfile
    tmp = tempfile.mkdtemp(prefix="frontdoor-drive-")
    spec_path = os.path.join(tmp, "graph.yaml")

    def worker_cmd(component: str) -> list[str]:
        return [
            sys.executable, "-m", "dynamo_tpu.mocker.main",
            "--model", MODEL, "--component", component,
            "--block-size", "16", "--num-gpu-blocks", "2048",
            "--max-num-seqs", "8", "--speedup-ratio", "4.0",
            "--migration-limit", "50",
        ]

    common_env = {
        "DYN_CONTROL_PLANE": addrs,
        "PYTHONPATH": os.pathsep.join(sys.path),
        "JAX_PLATFORMS": "cpu",
        "DYN_LEASE_TTL": "2",
        "DYN_DRAIN_TIMEOUT": "8",
        "DYN_LOG": "warning",
    }
    with open(spec_path, "w") as f:
        yaml.safe_dump({
            "apiVersion": "dynamo.tpu/v1alpha1",
            "kind": "DynamoGraphDeployment",
            "metadata": {"name": "frontdoor-drive"},
            "spec": {"services": {
                "prefill": {"replicas": n_prefill, "plannerRole": "prefill",
                            "command": worker_cmd("prefill"),
                            "env": dict(common_env)},
                "decode": {"replicas": n_decode, "plannerRole": "decode",
                           "command": worker_cmd("decode"),
                           "env": dict(common_env)},
            }},
        }, f)

    rt = await DistributedRuntime.create(
        plane=await RemoteControlPlane(addrs).connect())
    operator = aggregator = runner = None
    fe_procs: list = []
    drains: list = []
    results: list = []
    promoted_at: Optional[float] = None
    ticks_at_promotion = 0
    audit_cycles_post = (0, 0)
    kill_idx = 1
    hub_killed = False
    try:
        from dynamo_tpu.autoscale import (
            AutoscaleController, AutoscaleRunner, ObservationFuser,
            SloConfig, make_planner, plane_readiness,
        )
        from dynamo_tpu.planner.perf_interpolation import PerfInterpolator
        from dynamo_tpu.planner.prometheus import MultiPrometheusSource
        from dynamo_tpu.planner.virtual_connector import VirtualConnector
        from dynamo_tpu.router.publisher import MetricsAggregator

        operator = await ProcessOperator(
            spec_path, plane=rt.plane, tick_s=0.25, drain_timeout=10.0
        ).start()

        fe_env = {**os.environ, **common_env, **env_overrides}
        for i in range(n_frontends):
            proc, port, drain = await _spawn_frontend(i, fe_env)
            fe_procs.append((proc, port))
            drains.append(drain)
        urls = [f"http://127.0.0.1:{p}" for _, p in fe_procs]

        aggregator = await MetricsAggregator(
            rt.plane, stale_after_s=3.0).start()
        # the autoscale loop rides the FLEET scrape (MultiPrometheusSource:
        # per-replica deltas summed, dead replicas dropping out) — pinned
        # replica bounds, so the gate is "the loop keeps ticking through
        # both kills", not a scaling decision
        fuser = ObservationFuser(MultiPrometheusSource(urls), aggregator)
        slo = SloConfig.load()
        planner = make_planner(
            slo, PerfInterpolator([(1.0, 200.0), (4.0, 2500.0)]),
            PerfInterpolator([(24.0, 20.0), (72.0, 400.0)]),
            min_prefill_replicas=n_prefill, max_prefill_replicas=n_prefill,
            no_correction=True)

        async def readiness():
            return await plane_readiness(rt.plane, "dynamo")

        controller = AutoscaleController(
            slo, planner, fuser, VirtualConnector(rt.plane),
            readiness=readiness, metrics=rt.metrics, plane=rt.plane)
        runner = await AutoscaleRunner(controller).start()

        async with aiohttp.ClientSession() as session:
            # every replica must discover the model before traffic starts
            for url in urls:
                for _ in range(300):
                    try:
                        async with session.get(f"{url}/v1/models") as r:
                            doc = await r.json()
                        if any(m.get("id") == MODEL
                               for m in doc.get("data", [])):
                            break
                    except Exception:
                        pass
                    await asyncio.sleep(0.1)
                else:
                    raise RuntimeError(f"{url} never discovered {MODEL}")

            rng = np.random.default_rng(seed)
            import random as _random
            prompt_rng = _random.Random(seed)
            inflight: set = set()
            issued = 0
            fe_killed = False
            t0 = time.monotonic()
            while (now := time.monotonic() - t0) < duration_s:
                if not fe_killed and now >= 0.40 * duration_s:
                    # SIGKILL one replica mid-peak: no drain, no goodbye —
                    # its in-flight streams break and must be retried by
                    # the client, its worker-side seqs cancelled by
                    # response-plane peer death
                    os.kill(fe_procs[kill_idx][0].pid, 9)
                    fe_killed = True
                if not hub_killed and now >= 0.55 * duration_s:
                    await primary.stop()  # standby promotes under load
                    hub_killed = True
                    ticks_at_promotion = fuser.ticks
                    promoted_at = now
                rate = max(0.5, 2.0 + 3.0 * math.sin(
                    math.pi * now / duration_s))
                task = asyncio.get_running_loop().create_task(
                    stream_request_ha(
                        session, urls, MODEL,
                        make_prompt(prompt_rng, ISL_WORDS), OSL,
                        max_attempts=MAX_ATTEMPTS, backoff_s=0.5,
                        start=issued))
                issued += 1
                inflight.add(task)
                task.add_done_callback(
                    lambda t: (inflight.discard(t),
                               results.append(t.result())))
                await asyncio.sleep(float(rng.exponential(1.0 / rate)))
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)

            await _wait_for_async(lambda: not standby.is_standby,
                                  10.0, "standby promotion")

            survivors = [u for i, u in enumerate(urls) if i != kill_idx]

            async def _digest(url: str) -> Optional[dict]:
                try:
                    async with session.get(
                            f"{url}/v1/kv/digest",
                            timeout=aiohttp.ClientTimeout(total=3)) as r:
                        return await r.json()
                except Exception:
                    return None

            # settle: the survivors' per-worker radix digests must agree
            digests_agree = False
            resyncs_each: list = []
            last_docs: list = []
            for _ in range(60):
                docs = [await _digest(u) for u in survivors]
                last_docs = docs
                if all(d is not None for d in docs):
                    views = [d.get("models", {}).get(MODEL, {})
                             for d in docs]
                    if views[0] and all(v == views[0] for v in views[1:]):
                        digests_agree = True
                        resyncs_each = [
                            (d.get("cursors", {}).get(MODEL, {})
                             .get("resyncs_requested", 0)) for d in docs]
                        break
                await asyncio.sleep(0.25)
            if not digests_agree and os.environ.get("DYN_DRIVE_DEBUG"):
                print(f"DRIVE_DEBUG digests: {json.dumps(last_docs)}",
                      flush=True)

            # auditor continuing post-promotion: cycles advance
            async def _audit_cycles(url: str) -> int:
                try:
                    async with session.get(
                            f"{url}/v1/kv/audit",
                            timeout=aiohttp.ClientTimeout(total=3)) as r:
                        doc = await r.json()
                    return sum(int(m.get("cycles", 0))
                               for m in (doc.get("models") or doc).values()
                               if isinstance(m, dict))
                except Exception:
                    return -1

            c0 = await _audit_cycles(survivors[0])
            await asyncio.sleep(3.0)
            c1 = await _audit_cycles(survivors[0])
            audit_cycles_post = (c0, c1)

            # the dead replica's lease expires; survivors stay ready
            frontends_ready = -1
            fe_doc: dict = {}
            for _ in range(40):
                try:
                    async with session.get(
                            f"{survivors[0]}/v1/fleet/frontends") as r:
                        fe_doc = await r.json()
                    if fe_doc.get("count") == n_frontends - 1:
                        frontends_ready = fe_doc.get("ready", -1)
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.25)
            if frontends_ready < 0 and os.environ.get("DYN_DRIVE_DEBUG"):
                print(f"DRIVE_DEBUG frontends: {json.dumps(fe_doc)}",
                      flush=True)

            # fleet scorecard's cross-replica convergence check, from a
            # survivor's own point of view
            radix_check_ok = None
            try:
                async with session.get(
                        f"{survivors[0]}/v1/fleet/scorecard") as r:
                    scorecard_doc = await r.json()
                for c in scorecard_doc.get("checks", []):
                    if c.get("name") == "radix_replica_agreement":
                        radix_check_ok = bool(c.get("ok"))
            except Exception:
                pass

            # no-leak settle: with traffic stopped, a worker still
            # stepping an orphaned seq keeps publishing fresh metrics —
            # after the stale window, any non-stale active/waiting slot IS
            # a leak
            await asyncio.sleep(4.0)
            agg = aggregator.aggregate()
            leaked_seqs = (agg["requests_active"] + agg["requests_waiting"]
                           if agg["workers"] else 0)
            leaked_blocks = agg["kv_active_blocks"] if agg["workers"] else 0
        ticks_end = fuser.ticks
        fe_rc = fe_procs[kill_idx][0].returncode
    finally:
        if runner is not None:
            await runner.stop()
        if aggregator is not None:
            await aggregator.stop()
        for proc, _ in fe_procs:
            if proc.returncode is None:
                proc.terminate()
        for proc, _ in fe_procs:
            if proc.returncode is None:
                try:
                    await asyncio.wait_for(proc.wait(), 15.0)
                except asyncio.TimeoutError:
                    proc.kill()
        for d in drains:
            d.cancel()
        if operator is not None:
            await operator.stop()
        await rt.shutdown()
        await standby.stop()
        if not hub_killed:
            await primary.stop()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    ok = [r for r in results if r.ok]
    lost_tokens = sum(max(0, OSL - r.completion_tokens) for r in ok)
    dup_tokens = sum(max(0, r.completion_tokens - OSL) for r in ok)
    retried = [r for r in results if r.attempts > 1]
    errors: dict = {}
    for r in results:
        if not r.ok:
            key = (r.error or "?")[:80]
            errors[key] = errors.get(key, 0) + 1
    out = {
        "workload": (f"{len(results)} reqs over {duration_s:.0f}s, "
                     f"OSL {OSL}, {n_frontends} frontend replicas, "
                     f"fe-{kill_idx} SIGKILLed @40%, hub primary killed "
                     f"@55%"),
        "requests": len(results), "ok": len(ok),
        "failed": len(results) - len(ok),
        "failure_errors": errors,
        "retried": len(retried),
        "max_attempts_seen": max((r.attempts for r in results), default=0),
        "lost_tokens": lost_tokens,
        "dup_tokens": dup_tokens,
        "frontend_killed_rc": fe_rc,
        "hub_promoted": not standby.is_standby,
        "promoted_at_s": round(promoted_at, 2) if promoted_at else None,
        "digests_agree": digests_agree,
        "replica_resyncs": resyncs_each,
        "radix_check_ok": radix_check_ok,
        "frontends_ready_after": frontends_ready,
        "leaked_seqs": leaked_seqs,
        "leaked_blocks": leaked_blocks,
        "audit_cycles_post_promotion": list(audit_cycles_post),
        "autoscale_ticks_post_promotion": ticks_end - ticks_at_promotion,
    }
    gates = [
        out["failed"] == 0,
        len(retried) >= 1,                       # failover exercised
        out["max_attempts_seen"] <= MAX_ATTEMPTS,
        lost_tokens == 0 and dup_tokens == 0,
        out["hub_promoted"],
        digests_agree,
        all(r >= 1 for r in resyncs_each) and bool(resyncs_each),
        radix_check_ok is True,
        frontends_ready == n_frontends - 1,
        leaked_seqs == 0 and leaked_blocks == 0,
        audit_cycles_post[1] > audit_cycles_post[0] >= 0,
        out["autoscale_ticks_post_promotion"] >= 2,
    ]
    out["frontdoor_ok"] = all(gates)
    return out


async def _wait_for_async(predicate, timeout: float, msg: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.05)
    raise RuntimeError(f"timed out waiting for {msg}")


def main() -> None:
    from dynamo_tpu.runtime.config import setup_logging

    setup_logging()
    ap = argparse.ArgumentParser(
        description="flagship 70B-placement fleet drive (ISSUE 16)")
    ap.add_argument("--duration", type=float, default=40.0,
                    help="diurnal cycle seconds (default 40)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="fleet scale vs the 2+6 placement (default 1.0)")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--kill-error", type=float, default=0.0015,
                    help="per-step worker.kill probability on decode")
    ap.add_argument("--no-autoscale", action="store_true",
                    help="pin the fleet (bounded smoke mode)")
    ap.add_argument("--frontdoor", action="store_true",
                    help="run the front-door chaos leg instead (ISSUE 18: "
                         "3 frontend replicas, one SIGKILLed mid-peak, hub "
                         "primary killed once under live load)")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="also write the result document to FILE")
    cli = ap.parse_args()
    if cli.frontdoor:
        out = asyncio.run(frontdoor_drive(cli.duration, cli.seed))
        gate = out["frontdoor_ok"]
    else:
        out = asyncio.run(drive(cli.duration, cli.scale, cli.seed,
                                cli.kill_error,
                                autoscale=not cli.no_autoscale))
        gate = out["flagship_ok"]
    doc = json.dumps(out, indent=2, default=str)
    if cli.json:
        with open(cli.json, "w") as f:
            f.write(doc)
    # summary line without the full embedded scorecard
    slim = {k: v for k, v in out.items() if k != "scorecard"}
    print(json.dumps(slim, indent=2, default=str))
    raise SystemExit(0 if gate else 1)


if __name__ == "__main__":
    main()
