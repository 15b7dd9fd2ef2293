"""The engine worker as chipbench spawns it: ``dynamo_tpu.engine.main``
unchanged (``runpy``), plus a control thread that does the two things only
the process that holds the chip can do:

- ``<control>/trace.request`` ({"dir", "seconds"}) → a ``jax.profiler``
  trace that lasts until ``<control>/trace.stop`` appears (at most
  ``seconds``), then ``<control>/trace.done`` with the instants at which
  tracing was on and was stopped;
- ``<control>/mem.request`` → ``<control>/mem.json``, each local device's
  ``memory_stats()``.

Every run spawns the worker this way, traced or not, so the topology is the
same in both. Usage: ``worker_entry.py <control-dir> <engine.main args…>``.
"""

import json
import os
import runpy
import sys
import threading
import time


def _write(path: str, doc: dict):
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".tmp", path)


def _trace(control: str, req: dict):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # device ops + TraceAnnotations only
    stop = os.path.join(control, "trace.stop")
    jax.profiler.start_trace(req["dir"], profiler_options=opts)
    t0 = time.time()
    while time.time() - t0 < float(req["seconds"]) \
            and not os.path.exists(stop):
        time.sleep(0.005)
    t1 = time.time()
    jax.profiler.stop_trace()
    if os.path.exists(stop):
        os.remove(stop)
    _write(os.path.join(control, "trace.done"),
           {"on_epoch": t0, "stop_epoch": t1, "written_s": time.time() - t1})


def _mem(control: str, req: dict):
    import jax

    _write(os.path.join(control, "mem.json"), {"devices": [
        dict(d.memory_stats() or {}, id=d.id) for d in jax.local_devices()]})


def control_loop(control: str):
    while True:
        time.sleep(0.02)
        for name, act in (("trace.request", _trace), ("mem.request", _mem)):
            path = os.path.join(control, name)
            if not os.path.exists(path):
                continue
            try:
                with open(path) as f:
                    req = json.load(f)
                os.remove(path)
                act(control, req)
            except Exception as e:  # the harness reads this and fails the run
                _write(os.path.join(control, name.split(".")[0] + ".error"),
                       {"error": repr(e)})


if __name__ == "__main__":
    control = sys.argv[1]
    os.makedirs(control, exist_ok=True)
    threading.Thread(target=control_loop, args=(control,),
                     daemon=True).start()
    sys.argv = ["dynamo_tpu.engine.main", *sys.argv[2:]]
    runpy.run_module("dynamo_tpu.engine.main", run_name="__main__",
                     alter_sys=True)
