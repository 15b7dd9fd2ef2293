"""The three-process fleet a deployment runs — ``dynctl`` hub, engine worker,
OpenAI frontend — as child processes. Copied from ``chip_smoke.py`` (``Fleet``,
``http_get``, ``metric_samples``), which ran on the chip; what differs: the
worker is spawned through ``worker_entry.py``, its flags come from the
configuration file, the frontend starts beside the worker instead of after
it, and logs go under ``chiprun_out/chipbench/<workload>/``.

Nothing here imports JAX: a chip belongs to one process, the worker.
"""

import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def free_port() -> int:
    """A port free on EVERY local address: the hub and the frontend listen
    on 0.0.0.0, where a port that is free on 127.0.0.1 alone can still be
    held by a connection of the machine's own (my chip run, PR 24: one run
    in 26 died on "address already in use")."""
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def http_get(port: int, path: str, timeout: float = 60) -> str:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("GET", path)
    r = c.getresponse()
    body = r.read().decode()
    c.close()
    if r.status != 200:
        raise SystemExit(f"GET {path}: {r.status} {body[:300]}")
    return body


def metric_samples(text: str, name: str) -> dict:
    """{labels: value} of one family in a Prometheus text exposition."""
    found = {}
    for line in text.splitlines():
        m = re.match(rf"{re.escape(name)}(\{{[^}}]*\}})?\s+(\S+)$", line)
        if m:
            found[m.group(1) or ""] = float(m.group(2))
    return found


class Fleet:
    """hub + engine worker + frontend, logs and control files under
    ``work``."""

    def __init__(self, config: dict, work: str):
        os.makedirs(work, exist_ok=True)
        self.work, self.config = work, config
        self.model = config.get("model", "bench")
        self.control = os.path.join(work, "control")
        shutil.rmtree(self.control, ignore_errors=True)   # no stale answers
        self.procs: list = []
        self.hub_port, self.http_port = free_port(), free_port()
        self.sys_port = free_port()
        self.env = dict(os.environ, PYTHONPATH=ROOT, PYTHONUNBUFFERED="1",
                        DYN_CONTROL_PLANE=f"127.0.0.1:{self.hub_port}",
                        DYN_LOG="info")

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.http_port}"

    def spawn(self, name: str, argv: list, env=None):
        log = open(os.path.join(self.work, f"{name}.log"), "w")
        p = subprocess.Popen([sys.executable, *argv], cwd=ROOT,
                             env=env or self.env, stdout=log,
                             stderr=subprocess.STDOUT)
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
            time.sleep(0.2)

    def start(self, traced: bool, ready_timeout: float) -> dict:
        """Start the three processes; returns the worker's ``engine built:``
        facts plus the start-up times the harness clock saw."""
        t0 = time.monotonic()
        hub = self.spawn("hub", ["-m", "dynamo_tpu.runtime.dynctl",
                                 "--port", str(self.hub_port)])
        self.wait_for(hub, "dynctl listening", 60)
        wenv = dict(self.env, DYN_SYSTEM_PORT=str(self.sys_port),
                    **self.config.get("worker_env", {}))
        if traced:
            wenv["DYN_JAX_PROFILER"] = "1"   # dynamo.* step annotations
        worker = self.spawn(
            "worker", [os.path.join(HERE, "worker_entry.py"), self.control,
                       "--model", self.model, "--arch", self.config["arch"],
                       "--allow-test-metadata",
                       *self.config["worker_flags"]], env=wenv)
        front = self.spawn("frontend", ["-m", "dynamo_tpu.frontend.main",
                                        "--port", str(self.http_port)])
        log = self.wait_for(worker, "WORKER_READY", ready_timeout)
        t_worker = time.monotonic() - t0
        self.wait_for(front, "FRONTEND_READY", 120)
        deadline = time.monotonic() + 60
        while self.model not in http_get(self.http_port, "/v1/models"):
            if time.monotonic() > deadline:
                raise SystemExit("frontend never listed the worker's model")
            time.sleep(0.1)
        facts = json.loads(
            re.search(r"engine built: (\{.*\})", log).group(1))
        facts["worker_ready_s"] = t_worker
        facts["ready_s"] = time.monotonic() - t0
        return facts

    def worker_log(self) -> str:
        return open(os.path.join(self.work, "worker.log")).read()

    def tell_worker(self, request: str, doc: dict):
        """Drop ``<control>/<request>`` for worker_entry.py's control thread
        (written whole, then renamed into place)."""
        path = os.path.join(self.control, request)
        with open(path + ".tmp", "w") as f:
            json.dump(doc, f)
        os.replace(path + ".tmp", path)

    def ask_worker(self, request: str, answer: str, doc: dict,
                   timeout: float) -> dict:
        self.tell_worker(request, doc)
        return self.wait_answer(answer, timeout)

    def wait_answer(self, answer: str, timeout: float) -> dict:
        done = os.path.join(self.control, answer)
        err = os.path.join(self.control, answer.split(".")[0] + ".error")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for path in (done, err):
                if os.path.exists(path):
                    with open(path) as f:
                        doc = json.load(f)
                    if path == err:
                        raise SystemExit(f"worker: {answer}: {doc}")
                    return doc
            time.sleep(0.05)
        raise SystemExit(f"worker: no {answer} within {timeout:.0f}s")

    def stop(self):
        """SIGTERM frontend and worker, wait for both, then the hub (so the
        worker can deregister); a process that ignores it is killed."""
        for group in (self.procs[:0:-1], self.procs[:1]):
            for p in group:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in group:
                try:
                    p.wait(30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
