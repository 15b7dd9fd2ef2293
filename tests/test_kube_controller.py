"""DynamoGraphDeployment controller against the in-repo fake API server.

Every test drives the REAL wire contract over HTTP — list/watch with
resourceVersion resume, the status subresource, 409 conflicts, 410 watch
expiry — the envtest pattern the reference's Go operator uses
(ref: deploy/cloud/operator/internal/controller/)."""

import asyncio

import pytest

from dynamo_tpu.deploy.controller import (
    GROUP,
    LABEL_GRAPH,
    PLURAL,
    VERSION,
    DynamoGraphController,
)
from dynamo_tpu.deploy.fake_apiserver import FakeKubeApiServer
from dynamo_tpu.deploy.kube_api import Conflict, KubeClient, WatchExpired
from dynamo_tpu.deploy.kubernetes_connector import ApiKubernetesConnector
from dynamo_tpu.planner.planner_core import Decision

pytestmark = pytest.mark.anyio


def graph_cr(name="g1", prefill=1, decode=2):
    return {
        "apiVersion": f"{GROUP}/{VERSION}",
        "kind": "DynamoGraphDeployment",
        "metadata": {"name": name},
        "spec": {"services": {
            "prefill": {"replicas": prefill,
                        "command": ["python", "-m", "x", "--role", "prefill"]},
            "decode": {"replicas": decode,
                       "command": ["python", "-m", "x", "--role", "decode"]},
        }},
    }


async def _env():
    server = FakeKubeApiServer()
    base = await server.start()
    server.register(GROUP, VERSION, PLURAL, "DynamoGraphDeployment")
    client = KubeClient(base)
    return server, client


async def _wait(predicate, timeout=5.0, msg="condition"):
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        r = await predicate()
        if r:
            return r
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError(f"timed out waiting for {msg}")
        await asyncio.sleep(0.02)


async def _mutate_cr(crs, name, mutate, retries=5):
    """get→mutate→replace with retry-on-conflict: the live controller's
    status writes legitimately bump resourceVersion between the test's get
    and replace (the same RetryOnConflict idiom the controller uses)."""
    for _ in range(retries):
        cur = await crs.get(name)
        mutate(cur)
        try:
            return await crs.replace(name, cur)
        except Conflict:
            await asyncio.sleep(0.02)
    raise AssertionError(f"replace of {name} kept conflicting")


async def test_create_scale_and_status():
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(graph_cr(prefill=1, decode=2))

        async def pods_settled():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return lst["items"] if len(lst["items"]) == 3 else None
        items = await _wait(pods_settled, msg="3 pods")
        names = sorted(p["metadata"]["name"] for p in items)
        assert names == ["g1-decode-0", "g1-decode-1", "g1-prefill-0"]
        # ownerReferences point back at the CR (GC contract)
        assert items[0]["metadata"]["ownerReferences"][0]["name"] == "g1"

        # status subresource: observedGeneration + ready counts + Ready cond
        async def status_ready():
            obj = await crs.get("g1")
            st = obj.get("status") or {}
            conds = {c["type"]: c["status"] for c in st.get("conditions", [])}
            if conds.get("Ready") == "True":
                return obj
        obj = await _wait(status_ready, msg="Ready status")
        assert obj["status"]["services"] == {
            "prefill": {"desired": 1, "ready": 1},
            "decode": {"desired": 2, "ready": 2}}
        assert obj["status"]["observedGeneration"] == obj["metadata"]["generation"]

        # scale decode 2→4 via merge patch (what the planner does)
        await crs.patch("g1", {"spec": {"services": {
            "decode": {"replicas": 4}}}})

        async def scaled():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return len(lst["items"]) == 5 or None
        await _wait(scaled, msg="scale-up to 5 pods")

        # scale down 4→1: newest-first deletion keeps decode-0
        await crs.patch("g1", {"spec": {"services": {
            "decode": {"replicas": 1}}}})

        async def shrunk():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            names = sorted(p["metadata"]["name"] for p in lst["items"])
            return names if len(names) == 2 else None
        names = await _wait(shrunk, msg="scale-down to 2 pods")
        assert names == ["g1-decode-0", "g1-prefill-0"]
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_pod_death_is_healed_and_cr_delete_collects_pods():
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(graph_cr(prefill=0, decode=1))

        async def one_pod():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return lst["items"] or None
        (pod,) = await _wait(one_pod, msg="initial pod")

        # kubelet loses the pod → the watch nudges a reconcile → recreated
        await pods.delete(pod["metadata"]["name"])
        await _wait(one_pod, msg="pod recreated")

        await crs.delete("g1")

        async def gone():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return len(lst["items"]) == 0 or None
        await _wait(gone, msg="owned pods collected")
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_status_conflict_is_retried():
    """A write landing between the controller's read and status PUT forces
    a 409; the controller must re-read and win the retry."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    ctrl = DynamoGraphController(client)
    try:
        await crs.create(graph_cr(prefill=0, decode=0))
        # pre-add the controller's finalizer so reconcile() skips the
        # finalizer-ensure GET+PUT (it would consume a racing round)
        from dynamo_tpu.deploy.controller import FINALIZER
        await crs.patch("g1", {"metadata": {"finalizers": [FINALIZER]}})
        # interleave: bump the CR's rv after every GET the controller makes
        orig_get = crs.get
        bumped = {"n": 0}

        async def racing_get(name):
            obj = await orig_get(name)
            if bumped["n"] < 2:  # lose the first two rounds
                bumped["n"] += 1
                await crs.patch(name, {"metadata": {
                    "annotations": {"race": str(bumped['n'])}}})
            return obj
        crs.get = racing_get
        ctrl.crs = crs
        ctrl._cache["g1"] = await orig_get("g1")
        await ctrl.reconcile("g1")
        assert ctrl.status_conflicts_retried == 2
        obj = await orig_get("g1")
        assert obj["status"]["observedGeneration"] >= 1
    finally:
        await client.close()
        await server.stop()


async def test_watch_expiry_triggers_relist():
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    try:
        await crs.create(graph_cr(name="a"))
        # age the watch horizon far past rv=1
        kind = server._kinds[f"apis/{GROUP}/{VERSION}/{PLURAL}"]
        for _ in range(8):
            await crs.patch("a", {"metadata": {"annotations": {"x": "y"}}})
        kind.truncate(2)  # horizon now excludes rv=1

        with pytest.raises(WatchExpired):
            async for _ in crs.watch(resource_version="1"):
                pass

        # the controller handles this by relisting
        ctrl = await DynamoGraphController(client).start()
        try:
            await asyncio.sleep(0.1)
            assert ctrl.relists >= 1
            assert "a" in ctrl._cache
        finally:
            await ctrl.stop()
    finally:
        await client.close()
        await server.stop()


async def test_status_subresource_isolation():
    """Status writes can't change spec; spec patches can't smuggle status;
    generation bumps only on spec changes."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    try:
        await crs.create(graph_cr())
        g0 = (await crs.get("g1"))["metadata"]["generation"]

        await crs.patch_status("g1", {"services": {"decode": {"ready": 9}},
                                      "spec_smuggle": True})
        obj = await crs.get("g1")
        assert obj["spec"]["services"]["decode"]["replicas"] == 2  # untouched
        assert obj["metadata"]["generation"] == g0  # status ≠ generation bump

        await crs.patch("g1", {"status": {"hacked": True},
                               "spec": {"services": {"decode": {"replicas": 3}}}})
        obj = await crs.get("g1")
        assert "hacked" not in (obj.get("status") or {})
        assert obj["metadata"]["generation"] == g0 + 1  # spec change bumps
    finally:
        await client.close()
        await server.stop()


async def test_planner_connector_drives_controller_end_to_end():
    """planner Decision → API merge patch → controller watch → pods."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(graph_cr(prefill=1, decode=1))
        conn = ApiKubernetesConnector(client, "g1")
        await conn.apply(Decision(prefill_replicas=2, decode_replicas=3))

        async def settled():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return len(lst["items"]) == 5 or None
        await _wait(settled, msg="planner-driven scale")
        assert await conn.read_replicas() == {"prefill": 2, "decode": 3}
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_sla_planner_scales_pods_through_api_end_to_end():
    """The whole L7 loop over real HTTP: traffic observations → SLA planner
    Decision → API merge patch on the CRD → controller watch → pods. The
    reference's planner→operator→pods contract
    (ref: components/planner + deploy/cloud/operator), one process."""
    from dynamo_tpu.planner.perf_interpolation import PerfInterpolator
    from dynamo_tpu.planner.planner_core import (
        Observation, Planner, PlannerConfig,
    )

    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(graph_cr(prefill=1, decode=1))
        conn = ApiKubernetesConnector(client, "g1")
        planner = Planner(
            PlannerConfig(ttft_sla_ms=200.0, itl_sla_ms=20.0,
                          scale_down_patience=1),
            prefill_perf=PerfInterpolator(
                points=[[1.0, 100.0], [2.0, 180.0], [4.0, 400.0]]),
            decode_perf=PerfInterpolator(
                points=[[500.0, 10.0], [1000.0, 18.0], [2000.0, 45.0]]))

        # sustained heavy traffic → fleet must grow
        for _ in range(4):
            planner.observe(Observation(request_rate=40.0, isl=1000, osl=64))
        heavy = planner.compute()
        assert heavy.prefill_replicas > 1 and heavy.decode_replicas > 1
        await conn.apply(heavy)

        async def n_pods(want):
            async def check():
                lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
                return len(lst["items"]) == want or None
            return check
        await _wait(await n_pods(heavy.prefill_replicas + heavy.decode_replicas),
                    msg="scale-up pods")

        # traffic collapses → fleet shrinks (patience=1)
        for _ in range(6):
            planner.observe(Observation(request_rate=0.2, isl=200, osl=16))
            light = planner.compute()
        assert light.prefill_replicas < heavy.prefill_replicas
        await conn.apply(light)
        await _wait(await n_pods(light.prefill_replicas + light.decode_replicas),
                    msg="scale-down pods")
        # CRD spec reflects the last applied decision
        assert await conn.read_replicas() == {
            "prefill": light.prefill_replicas,
            "decode": light.decode_replicas}
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


def gang_cr(name="mh", workers=2, nodes=4):
    """A multi-host service: each replica is a gang of ``nodes`` pods."""
    return {
        "apiVersion": f"{GROUP}/{VERSION}",
        "kind": "DynamoGraphDeployment",
        "metadata": {"name": name},
        "spec": {"services": {
            "worker": {"replicas": workers, "multinode": nodes,
                       "command": ["python", "-m", "w"]},
        }},
    }


async def test_gang_create_all_or_nothing_and_scale_down():
    """multinode services place whole pod gangs (ref: podgangset.go):
    members carry rank/count/leader env, a replica is ready only when
    every member runs, scale-down removes whole gangs newest-first."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(gang_cr(workers=2, nodes=3))

        async def settled(n):
            async def p():
                lst = await pods.list(label_selector=f"{LABEL_GRAPH}=mh")
                return lst["items"] if len(lst["items"]) == n else None
            return await _wait(p, msg=f"{n} pods")
        items = await settled(6)
        names = sorted(p["metadata"]["name"] for p in items)
        assert names == [f"mh-worker-{r}-{h}" for r in range(2)
                         for h in range(3)]
        env0 = {e["name"]: e["value"] for e in
                items[0]["spec"]["containers"][0]["env"]}
        assert env0["DYN_MH_RANK"] == "0" and env0["DYN_MH_COUNT"] == "3"
        assert env0["DYN_MH_LEADER"] == "mh-worker-0-0"
        assert env0["DYN_POD_NAME"] == "mh-worker-0-0"
        gangs = {p["metadata"]["labels"]["dynamo.tpu/gang"] for p in items}
        assert gangs == {"mh-worker-0", "mh-worker-1"}

        async def status_ready():
            obj = await crs.get("mh")
            st = obj.get("status") or {}
            svc = (st.get("services") or {}).get("worker") or {}
            return svc if svc.get("ready") == 2 else None
        await _wait(status_ready, msg="both gangs ready")

        # scale down 2 -> 1: the NEWEST whole gang goes, none of gang 0
        def one_gang(cur):
            cur["spec"]["services"]["worker"]["replicas"] = 1
        await _mutate_cr(crs, "mh", one_gang)
        items = await settled(3)
        assert {p["metadata"]["name"] for p in items} == {
            "mh-worker-0-0", "mh-worker-0-1", "mh-worker-0-2"}
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_partial_gang_is_rolled_back():
    """A gang member failing to place (quota) rolls back the whole gang —
    a partially scheduled multi-host worker never starts."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    # fail the 3rd member of gang 1 a few times (reconcile retries)
    server.fail_create = ("mh-worker-1-2", 3)
    ctrl = await DynamoGraphController(client).start()
    try:
        await crs.create(gang_cr(workers=2, nodes=3))

        async def gang0_up():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=mh")
            names = {p["metadata"]["name"] for p in lst["items"]}
            return names if {"mh-worker-0-0", "mh-worker-0-1",
                             "mh-worker-0-2"} <= names else None
        names = await _wait(gang0_up, msg="gang 0 placed")
        # while the quota injection holds, gang 1 must be all-or-nothing.
        # A partial set IS briefly observable inside the create→rollback
        # window (separate HTTP calls); what must never happen is a partial
        # gang PERSISTING — flag only a partial set seen twice in a row.
        prev = None
        for _ in range(12):
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=mh")
            g1 = frozenset(p["metadata"]["name"] for p in lst["items"]
                           if p["metadata"]["labels"].get("dynamo.tpu/gang")
                           == "mh-worker-1")
            partial = g1 and g1 != frozenset(
                {"mh-worker-1-0", "mh-worker-1-1", "mh-worker-1-2"})
            assert not (partial and g1 == prev), f"partial gang persisted: {g1}"
            prev = g1 if partial else None
            await asyncio.sleep(0.07)

        # once quota clears, the requeue loop completes gang 1 IN ITS OWN
        # slot — no stray higher-index gangs from the failed attempts
        async def all_up():
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=mh")
            names = sorted(p["metadata"]["name"] for p in lst["items"])
            return names == [f"mh-worker-{r}-{h}" for r in range(2)
                             for h in range(3)] or None
        await _wait(all_up, timeout=10.0, msg="gang 1 completes in slot 1")
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_scale_down_cleans_discovery_keys():
    """Scale-down deletes the removed pods' instances/ keys immediately,
    and a service removed from the spec loses its whole discovery subtree
    (ref: operator/internal/etcd/etcd.go:34, DeleteKeys by prefix)."""
    import msgpack

    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    server, client = await _env()
    plane = LocalControlPlane()

    def inst_val(pod):
        return msgpack.packb({"namespace": "dynamo", "component": "c",
                              "endpoint": "e", "lease": 1,
                              "metadata": {"pod": pod}})

    # discovery keys as live workers would write them, one per pod
    await plane.kv_put("instances/dynamo/decode/e:aa", inst_val("g1-decode-0"))
    await plane.kv_put("instances/dynamo/decode/e:bb", inst_val("g1-decode-1"))
    await plane.kv_put("instances/dynamo/prefill/e:cc",
                       inst_val("g1-prefill-0"))

    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client, plane=plane).start()
    try:
        await crs.create(graph_cr(prefill=1, decode=2))

        async def n_pods(n):
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
            return len(lst["items"]) == n or None
        await _wait(lambda: n_pods(3), msg="3 pods")

        # scale decode 2 -> 1: victim's key goes, survivor's stays
        def scale_down(cur):
            cur["spec"]["services"]["decode"]["replicas"] = 1

        await _mutate_cr(crs, "g1", scale_down)
        await _wait(lambda: n_pods(2), msg="scale down")

        async def victim_key_gone():
            keys = await plane.kv_get_prefix("instances/dynamo/")
            return ("instances/dynamo/decode/e:bb" not in keys) or None
        await _wait(victim_key_gone, msg="victim discovery key removed")
        keys = await plane.kv_get_prefix("instances/dynamo/")
        assert "instances/dynamo/decode/e:aa" in keys
        assert "instances/dynamo/prefill/e:cc" in keys

        # remove the prefill service entirely -> its subtree is wiped
        def drop_prefill(cur):
            del cur["spec"]["services"]["prefill"]

        await _mutate_cr(crs, "g1", drop_prefill)

        async def prefill_gone():
            keys = await plane.kv_get_prefix("instances/dynamo/")
            return all(not k.startswith("instances/dynamo/prefill/")
                       for k in keys) or None
        await _wait(prefill_gone, msg="prefill subtree wiped")
        keys = await plane.kv_get_prefix("instances/dynamo/")
        assert "instances/dynamo/decode/e:aa" in keys  # untouched
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_single_to_multinode_migration_replaces_legacy_pods():
    """Switching a service to multinode must retire the legacy single-node
    pods and form proper gangs — not wedge on unparseable names."""
    server, client = await _env()
    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client).start()
    try:
        cr = {
            "apiVersion": f"{GROUP}/{VERSION}",
            "kind": "DynamoGraphDeployment",
            "metadata": {"name": "mig"},
            "spec": {"services": {"worker": {"replicas": 2,
                                             "command": ["w"]}}},
        }
        await crs.create(cr)

        async def names_are(expect):
            lst = await pods.list(label_selector=f"{LABEL_GRAPH}=mig")
            names = sorted(p["metadata"]["name"] for p in lst["items"])
            return names == expect or None
        await _wait(lambda: names_are(["mig-worker-0", "mig-worker-1"]),
                    msg="single-node pods")

        def to_multinode(cur):
            cur["spec"]["services"]["worker"] = {
                "replicas": 1, "multinode": 2, "command": ["w"]}
        await _mutate_cr(crs, "mig", to_multinode)
        await _wait(lambda: names_are(["mig-worker-0-0", "mig-worker-0-1"]),
                    timeout=10.0, msg="gangs replace legacy pods")
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()


async def test_finalizer_pins_cr_until_cleanup_done():
    """The controller's finalizer (ref: controller_common/finalizer.go)
    keeps a deleted CR terminating until pods and discovery keys are
    gone — even across a controller restart mid-delete."""
    import msgpack

    from dynamo_tpu.deploy.controller import FINALIZER
    from dynamo_tpu.runtime.control_plane import LocalControlPlane

    server, client = await _env()
    plane = LocalControlPlane()
    await plane.kv_put(
        "instances/dynamo/decode/e:aa",
        msgpack.packb({"metadata": {"pod": "g1-decode-0"}}))

    crs = client.resource(GROUP, VERSION, "default", PLURAL)
    pods = client.resource("", "v1", "default", "pods")
    ctrl = await DynamoGraphController(client, plane=plane).start()
    try:
        await crs.create(graph_cr(prefill=0, decode=1))

        async def finalized():
            obj = await crs.get("g1")
            return FINALIZER in (obj["metadata"].get("finalizers") or []) \
                or None
        await _wait(finalized, msg="finalizer added")

        # stop the controller BEFORE deleting: the delete only marks the
        # CR terminating (finalizer holds it)
        await ctrl.stop()
        await crs.delete("g1")
        obj = await crs.get("g1")
        assert obj["metadata"].get("deletionTimestamp")

        # a fresh controller (restart) finishes the teardown: pods and
        # discovery keys collected, finalizer released, CR gone
        ctrl = await DynamoGraphController(client, plane=plane).start()

        async def cr_gone():
            try:
                await crs.get("g1")
                return None
            except Exception:
                return True
        await _wait(cr_gone, msg="CR collected after finalizer release")
        lst = await pods.list(label_selector=f"{LABEL_GRAPH}=g1")
        assert lst["items"] == []
        keys = await plane.kv_get_prefix("instances/dynamo/")
        assert keys == {}
    finally:
        await ctrl.stop()
        await client.close()
        await server.stop()
