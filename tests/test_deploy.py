"""Deploy layer: process operator reconciliation, Kubernetes connector,
Prometheus metrics source (ref: deploy/cloud/operator reconcilers,
planner kubernetes_connector.py, planner/utils/prometheus.py)."""

import asyncio
import json
import os
import sys
import time

import pytest

from dynamo_tpu.deploy.kubernetes_connector import KubernetesConnector
from dynamo_tpu.deploy.operator import ProcessOperator, parse_spec
from dynamo_tpu.planner.planner_core import Decision, Observation
from dynamo_tpu.planner.prometheus import (
    PrometheusMetricsSource, parse_prometheus_text,
)

pytestmark = pytest.mark.anyio

SLEEPER = [sys.executable, "-c",
           "import time\nwhile True: time.sleep(0.2)"]


def write_spec(path, services: dict) -> None:
    import yaml

    doc = {"apiVersion": "dynamo.tpu/v1alpha1",
           "kind": "DynamoGraphDeployment",
           "metadata": {"name": "t"},
           "spec": {"services": services}}
    with open(path, "w") as f:
        yaml.safe_dump(doc, f)


def alive(op: ProcessOperator, svc: str) -> int:
    return sum(1 for r in op.replicas[svc] if r.proc.poll() is None)


async def test_operator_scale_and_crash_restart(tmp_path):
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"work": {"replicas": 2, "command": SLEEPER,
                               "env": {"X_TEST": "1"}}})
    op = ProcessOperator(spec, tick_s=0.1)
    try:
        op.reconcile_once()
        assert alive(op, "work") == 2
        status = json.load(open(spec + ".status.json"))
        assert status["services"]["work"]["ready"] == 2

        # crash one replica → reaped, restart counted, respawned (after
        # backoff; force the clock past it)
        op.replicas["work"][0].proc.kill()
        op.replicas["work"][0].proc.wait()
        op.reconcile_once()
        assert op.restarts["work"] == 1
        op._next_start[("work", 0)] = 0.0
        op.reconcile_once()
        assert alive(op, "work") == 2

        # spec edit → scale down to 1 (newest killed first)
        write_spec(spec, {"work": {"replicas": 1, "command": SLEEPER}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        assert alive(op, "work") == 1
    finally:
        await op.stop()
    assert alive(op, "work") == 0  # drained


async def test_operator_backoff_is_per_slot_not_per_service(tmp_path):
    """Flagship-drive regression: chaos kills spread across a pool must
    not accumulate into one service-wide crash streak that freezes ALL
    respawns (observed as the decode pool collapsing to 1 alive while
    desired was 4). Each replica slot carries its own backoff."""
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"work": {"replicas": 3, "command": SLEEPER}})
    op = ProcessOperator(spec, tick_s=0.1)
    try:
        op.reconcile_once()
        assert alive(op, "work") == 3
        t0 = time.monotonic()
        for i in range(3):  # one independent death per slot
            victim = next(r for r in op.replicas["work"] if r.index == i)
            victim.proc.kill()
            victim.proc.wait()
            op.reconcile_once()
        # every slot is a FIRST offense (~1s delay each) — no shared
        # streak escalating toward the 5s/10s/30s tiers
        for i in range(3):
            assert op._crash_streak[("work", i)] == 1
            assert op._next_start[("work", i)] - t0 < 3.0
        # a slot whose delay elapsed respawns even while the others are
        # still backing off
        op._next_start[("work", 0)] = 0.0
        op.reconcile_once()
        assert alive(op, "work") == 1
        assert {r.index for r in op.replicas["work"]
                if r.proc.poll() is None} == {0}
        for slot in list(op._next_start):
            op._next_start[slot] = 0.0
        op.reconcile_once()
        assert alive(op, "work") == 3
    finally:
        await op.stop()


async def test_operator_follows_planner_target(tmp_path):
    from dynamo_tpu.planner.virtual_connector import VirtualConnector
    from dynamo_tpu.runtime import DistributedRuntime

    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {
        "decode": {"replicas": 1, "command": SLEEPER, "plannerRole": "decode"},
        "aux": {"replicas": 1, "command": SLEEPER},
    })
    rt = await DistributedRuntime.create()
    op = await ProcessOperator(spec, plane=rt.plane, tick_s=0.05).start()
    try:
        for _ in range(40):
            if alive(op, "decode") == 1:
                break
            await asyncio.sleep(0.05)
        assert alive(op, "decode") == 1

        # the planner writes a target; the operator must realize it
        await VirtualConnector(rt.plane).apply(
            Decision(prefill_replicas=0, decode_replicas=3))
        for _ in range(100):
            if alive(op, "decode") == 3:
                break
            await asyncio.sleep(0.05)
        assert alive(op, "decode") == 3
        assert alive(op, "aux") == 1  # non-planner service untouched
    finally:
        await op.stop()
        await rt.shutdown()


def test_spec_validation(tmp_path):
    bad = tmp_path / "bad.yaml"
    bad.write_text("kind: Nope\n")
    with pytest.raises(ValueError):
        parse_spec(str(bad))
    bad.write_text(
        "kind: DynamoGraphDeployment\nspec:\n  services:\n    a: {replicas: 1}\n")
    with pytest.raises(ValueError):  # no command
        parse_spec(str(bad))


async def test_kubernetes_connector_patches():
    calls = []
    state = {"prefill": 1, "decode": 1}

    async def fake_kubectl(argv):
        calls.append(argv)
        if argv[2] == "patch":
            patch = json.loads(argv[-1])
            for name, svc in patch["spec"]["services"].items():
                state[name] = svc["replicas"]
            return 0, "patched"
        if argv[2] == "get":
            return 0, json.dumps({"spec": {"services": {
                n: {"replicas": r} for n, r in state.items()}}})
        return 1, "unknown"

    c = KubernetesConnector("graph", k8s_namespace="serving",
                            runner=fake_kubectl)
    await c.apply(Decision(prefill_replicas=2, decode_replicas=5))
    assert state == {"prefill": 2, "decode": 5}
    assert calls[0][:2] == ["-n", "serving"]

    # unchanged decision → no second patch
    await c.apply(Decision(prefill_replicas=2, decode_replicas=5))
    assert len(calls) == 1
    assert await c.read_replicas() == {"prefill": 2, "decode": 5}

    # failed patch keeps .applied unset so the next tick retries
    async def failing(argv):
        return 1, "rbac denied"

    c2 = KubernetesConnector("graph", runner=failing)
    await c2.apply(Decision(prefill_replicas=3, decode_replicas=3))
    assert c2.applied is None


async def test_prometheus_source_deltas():
    samples = []

    def text(finished, prompt, completion, lat_sum, lat_cnt, ttft_sum, ttft_cnt):
        return "\n".join([
            f'dynamo_llm_requests_finished_total{{model="m"}} {finished}',
            f'dynamo_llm_prompt_tokens_total{{model="m"}} {prompt}',
            f'dynamo_llm_completion_tokens_total{{model="m"}} {completion}',
            f"dynamo_http_request_duration_seconds_sum {lat_sum}",
            f"dynamo_http_request_duration_seconds_count {lat_cnt}",
            f"dynamo_http_time_to_first_token_seconds_sum {ttft_sum}",
            f"dynamo_http_time_to_first_token_seconds_count {ttft_cnt}",
        ])

    src = PrometheusMetricsSource("http://unused:0")

    async def fake_fetch():
        return parse_prometheus_text(samples.pop(0))

    src._fetch = fake_fetch
    samples.append(text(10, 5000, 1000, 10.0, 10, 1.0, 10))
    assert await src() is None  # first sample: no deltas
    # +20 requests, +16000 prompt tokens, +4000 completion tokens
    samples.append(text(30, 21000, 5000, 110.0, 30, 3.0, 30))
    src._prev_t -= 10.0  # pretend 10s elapsed
    obs = await src()
    assert obs is not None
    assert abs(obs.request_rate - 2.0) < 0.2
    assert abs(obs.isl - 800.0) < 1e-6
    assert abs(obs.osl - 200.0) < 1e-6
    assert abs(obs.ttft_ms - 100.0) < 1e-6  # 2s Δsum / 20 Δcount
    # mean latency 5000ms; (5000-100)/(200-1) ≈ 24.6ms ITL
    assert 20.0 < obs.itl_ms < 30.0


def test_recipes_parse():
    for name in ("mocker-demo", "llama3-70b-v5e64-disagg",
                 "deepseek-r1-wideep"):
        svcs = parse_spec(f"deploy/recipes/{name}.yaml")
        assert svcs and all(s.command for s in svcs.values())
    assert parse_spec(
        "deploy/recipes/llama3-70b-v5e64-disagg.yaml")["decode"].planner_role == "decode"


async def test_operator_restarts_on_command_change(tmp_path):
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"work": {"replicas": 1, "command": SLEEPER}})
    op = ProcessOperator(spec, tick_s=0.1)
    try:
        op.reconcile_once()
        pid_before = op.replicas["work"][0].proc.pid
        # change the env (same replica count): replica must be replaced
        write_spec(spec, {"work": {"replicas": 1, "command": SLEEPER,
                                   "env": {"NEW": "cfg"}}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        assert alive(op, "work") == 1
        assert op.replicas["work"][0].proc.pid != pid_before
    finally:
        await op.stop()


@pytest.mark.slow
async def test_kubectl_contract_full_surface(tmp_path, monkeypatch):
    """The k8s path with a REAL subprocess against a fake kubectl binary
    (r2 verdict #10: no cluster in this environment, so the full CLI/JSON
    surface is pinned by contract): CRD + recipe manifests apply, the
    connector's merge patches mutate the stored resource, reads observe
    them, and the recorded argv sequence is exactly what a cluster would
    receive."""
    import subprocess

    import yaml

    state = tmp_path / "k8s-state.json"
    log = tmp_path / "kubectl-argv.jsonl"
    fake = tmp_path / "bin" / "kubectl"
    fake.parent.mkdir()
    fake.write_text(f"""#!{sys.executable}
import json, sys, yaml
STATE, LOG = {str(state)!r}, {str(log)!r}
args = sys.argv[1:]
open(LOG, "a").write(json.dumps(args) + "\\n")
try:
    store = json.load(open(STATE))
except FileNotFoundError:
    store = {{}}

def merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            merge(dst[k], v)
        else:
            dst[k] = v

ns = "default"
if args[:1] == ["-n"]:
    ns, args = args[1], args[2:]
cmd = args[0]
if cmd == "apply" and args[1] == "-f":
    for doc in yaml.safe_load_all(open(args[2])):
        if not doc:
            continue
        key = f"{{ns}}/{{doc['kind'].lower()}}/{{doc['metadata']['name']}}"
        store[key] = doc
        print(f"{{doc['kind'].lower()}}/{{doc['metadata']['name']}} configured")
elif cmd == "patch":
    key = f"{{ns}}/{{args[1]}}/{{args[2]}}"
    assert args[3:5] == ["--type", "merge"], args
    assert args[5] == "-p"
    if key not in store:
        print(f"Error: {{args[1]}} {{args[2]}} not found"); sys.exit(1)
    merge(store[key], json.loads(args[6]))
    print("patched")
elif cmd == "get":
    key = f"{{ns}}/{{args[1]}}/{{args[2]}}"
    assert args[3:5] == ["-o", "json"], args
    if key not in store:
        print("NotFound"); sys.exit(1)
    print(json.dumps(store[key]))
else:
    print(f"unknown command {{cmd}}"); sys.exit(1)
json.dump(store, open(STATE, "w"))
""")
    fake.chmod(0o755)
    monkeypatch.setenv("PATH", f"{fake.parent}:{os.environ['PATH']}")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    crd = os.path.join(repo, "deploy", "recipes", "k8s", "crd.yaml")
    gke = os.path.join(repo, "deploy", "recipes", "k8s",
                       "llama3-70b-gke.yaml")
    graph = os.path.join(repo, "deploy", "recipes",
                         "llama3-70b-v5e64-disagg.yaml")
    # the real yamls (CRD + raw GKE resources + the graph CR) apply
    # cleanly through the fake cluster
    for f in (crd, gke, graph):
        r = subprocess.run(["kubectl", "-n", "serving", "apply", "-f", f],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
    # the graph resource's kind matches the CRD it rides on
    crd_doc = next(iter(yaml.safe_load_all(open(crd))))
    graph_doc = next(iter(yaml.safe_load_all(open(graph))))
    assert graph_doc["kind"] == crd_doc["spec"]["names"]["kind"]
    graph_name = graph_doc["metadata"]["name"]

    # the connector's DEFAULT runner (real kubectl subprocess) scales it
    c = KubernetesConnector(graph_name, k8s_namespace="serving")
    await c.apply(Decision(prefill_replicas=4, decode_replicas=12))
    got = await c.read_replicas()
    assert got and got.get(c.prefill_service) == 4
    assert got.get(c.decode_service) == 12

    # pin the exact wire surface the cluster saw
    argvs = [json.loads(line) for line in open(log)]
    patch_argv = next(a for a in argvs if "patch" in a)
    assert patch_argv[:6] == ["-n", "serving", "patch",
                              "dynamographdeployment", graph_name, "--type"]
    assert json.loads(patch_argv[-1]) == {"spec": {"services": {
        "prefill": {"replicas": 4}, "decode": {"replicas": 12}}}}
    get_argv = argvs[-1]
    assert get_argv == ["-n", "serving", "get", "dynamographdeployment",
                        graph_name, "-o", "json"]


async def test_operator_scale_down_revokes_leases(tmp_path):
    """The reference's etcd-cleanup-on-scale-down contract: killing a
    replica must revoke its leases so discovery forgets the instance
    (ref: deploy/cloud/operator — here it falls out of lease semantics)."""
    from dynamo_tpu.runtime.control_plane import ControlPlaneServer

    server = ControlPlaneServer(port=0)
    addr = await server.start()
    worker_py = (
        "import asyncio\n"
        "from dynamo_tpu.runtime import DistributedRuntime\n"
        "async def main():\n"
        "    rt = await DistributedRuntime.create()\n"
        "    ep = rt.namespace('prod').component('w').endpoint('gen')\n"
        "    async def h(req, ctx):\n"
        "        yield {}\n"
        "    await ep.serve_endpoint(h)\n"
        "    await asyncio.sleep(120)\n"
        "asyncio.run(main())\n")
    spec = str(tmp_path / "graph.yaml")
    write_spec(spec, {"w": {
        "replicas": 2, "command": [sys.executable, "-c", worker_py],
        "env": {"DYN_CONTROL_PLANE": addr,
                "PYTHONPATH": os.pathsep.join(sys.path)}}})

    from dynamo_tpu.runtime import DistributedRuntime
    os.environ["DYN_CONTROL_PLANE"] = addr
    try:
        rt = await DistributedRuntime.create()
        client = await rt.namespace("prod").component("w").endpoint(
            "gen").client().start()
        op = ProcessOperator(spec, tick_s=0.1)
        op.reconcile_once()
        for _ in range(200):
            if len(client.instance_ids()) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 2

        write_spec(spec, {"w": {
            "replicas": 1, "command": [sys.executable, "-c", worker_py],
            "env": {"DYN_CONTROL_PLANE": addr,
                    "PYTHONPATH": os.pathsep.join(sys.path)}}})
        os.utime(spec, (time.time() + 2, time.time() + 2))
        op.reconcile_once()
        # the killed replica's disconnect revokes its lease → discovery
        # forgets the instance without any explicit cleanup call
        for _ in range(200):
            if len(client.instance_ids()) == 1:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 1
        await op.stop()
        await rt.shutdown()
    finally:
        os.environ.pop("DYN_CONTROL_PLANE", None)
        await server.stop()
