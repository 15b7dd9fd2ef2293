"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

    python chipbench/trace_reduce.py <trace-dir-or-file> --kind "TPU v5 lite"
    python chipbench/trace_reduce.py <trace-dir-or-file> --dump   # by hand

Reads the file with ``jax.profiler.ProfileData`` (no device needed; the
harness calls this only after the worker has released the chip). What it
yields, per device plane (``/device:TPU:<n>``), from its ``XLA Ops`` line:

- ``busy_s``: the union of the op intervals, ``window_s``: the traced
  window — the span the worker had tracing on (``asked_s``) or, if longer,
  first op start to last op end over all devices and the ``dynamo.*`` host
  annotations — so idle share = 1 − busy / window, idle at the edges
  included;
- self time by op (an op's duration minus the ops nested in it: the layer
  scan's ``while`` holds its body). An event is named by its HLO text,
  ``%name = shape opcode(operands…)``; ops are grouped by ``%name`` over the
  token-bucket programs. The kernel is the op NAMED ``ragged_paged_attention``
  (not one that merely takes its result), collectives by their opcode names;
- steps: executions on the ``XLA Modules`` line that last 1 ms or more (the
  step programs; sampling and the eager slices between steps take
  microseconds), the fewest over the devices. ``dynamo.*`` host annotations
  are counted beside them: one
  ``dynamo.decode_pipeline`` spans many pipelined decode steps;
- ``breakdown``: the ten ops with most self time (first device) and the five
  longest idle gaps on the device that idled most, each tagged "inside
  dynamo.<x>" or "between steps" by where its midpoint falls.

The device kind has to be in ``peaks.json``: an unknown kind is an error,
not a default.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_MIN_NS = 1_000_000
LABEL_CHARS = 120
KERNEL = "ragged_paged_attention"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
ANNOTATION = "dynamo."


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json "
                         f"(has {sorted(table)}): add it with its source")
    return table[kind]


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit(f"no *.xplane.pb under {path}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def op_name(event_name: str) -> str:
    """``%fusion.146`` of ``%fusion.146 = bf16[8,14336]{…} fusion(…)``."""
    return event_name.split(" = ", 1)[0]


def _events(line) -> list:
    """(start_ns, end_ns, name) of a line's events, sorted so that a parent
    comes before the events nested in it."""
    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
          for e in line.events]
    ev.sort(key=lambda x: (x[0], -x[1]))
    return ev


def union_and_self(ev: list):
    """One pass over sorted events: the merged busy intervals, and each
    event's self time (duration minus what is nested directly in it),
    summed by name."""
    merged, by_name = [], {}
    stack = []   # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            by_name[name] = by_name.get(name, 0) + max(0, self_ns)

    for start, end, name in ev:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    close(float("inf"))
    return merged, by_name


def is_collective(name: str) -> bool:
    return any(c in op_name(name) for c in COLLECTIVES)


def is_kernel(name: str) -> bool:
    return KERNEL in op_name(name)


def by_op(self_by_event: dict) -> dict:
    """Self time grouped by ``%name`` over the programs it appears in:
    {op: [ns, label]}, the label being the HLO text of its costliest
    variant, cut to ``LABEL_CHARS``."""
    out, best = {}, {}
    for name, ns in self_by_event.items():
        op = op_name(name)
        out[op] = out.get(op, 0) + ns
        if ns > best.get(op, (0, ""))[0]:
            best[op] = (ns, name)
    return {op: [ns, best[op][1][:LABEL_CHARS]] for op, ns in out.items()}


def reduce_trace(path: str, kind: str, asked_s: float = 0.0) -> dict:
    peaks_for(kind)
    pd = load(path)
    devices, modules, annotations = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events
                                if e.name.startswith(ANNOTATION)]
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        raise SystemExit("the trace holds no device operation "
                         f"(planes: {[p.name for p in pd.planes]})")
    annotations.sort()
    w0 = min([ev[0][0] for ev in devices.values()]
             + [a[0] for a in annotations])
    w1 = max([max(e[1] for e in ev) for ev in devices.values()]
             + [a[1] for a in annotations])
    window_ns = max(w1 - w0, int(asked_s * 1e9))
    per_device, merged_by_dev, self_by_dev = {}, {}, {}
    for name, ev in sorted(devices.items()):
        merged, by_event = union_and_self(ev)
        merged_by_dev[name], self_by_dev[name] = merged, by_event
        busy = sum(b - a for a, b in merged)
        per_device[name] = {
            "busy_s": busy / 1e9,
            "idle_share": 1.0 - busy / window_ns,
            "events": len(ev),
            "kernel_s": sum(v for k, v in by_event.items()
                            if is_kernel(k)) / 1e9,
            "collective_s": sum(v for k, v in by_event.items()
                                if is_collective(k)) / 1e9,
            "steps": sum(1 for a, b, _ in modules.get(name, [])
                         if b - a >= STEP_MIN_NS),
        }
    worst = max(per_device, key=lambda k: per_device[k]["idle_share"])
    first = sorted(per_device)[0]
    noted = {}
    for a0, _, name in annotations:
        noted[name] = noted.get(name, 0) + 1
    return {
        "kind": kind,
        "window_s": window_ns / 1e9,
        "devices": per_device,
        "busy_s_mean": sum(d["busy_s"] for d in per_device.values())
        / len(per_device),
        "worst_idle_device": worst,
        "first_device": first,
        # a step program runs on every device; the first one also runs the
        # sampler's and other one-device programs
        "steps_total": min(d["steps"] for d in per_device.values()),
        "annotations": noted,
        "kernel_on_device": any(is_kernel(k) for k in self_by_dev[first]),
        "breakdown": {
            "device_ops": top_ops(by_op(self_by_dev[first]), 10),
            "idle_gaps": idle_gaps(merged_by_dev[worst], annotations,
                                   w0, w0 + window_ns, 5),
        },
    }


def top_ops(ops: dict, n: int) -> list:
    top = sorted(ops.values(), key=lambda v: -v[0])[:n]
    return [[label, ns / 1e9] for ns, label in top]


def idle_gaps(merged: list, annotations: list, w0: int, w1: int,
              n: int) -> list:
    """The longest gaps between busy intervals, each tagged by whether its
    midpoint lies inside a ``dynamo.*`` host annotation."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    out = []
    for dur, a, b in gaps[:n]:
        mid = (a + b) // 2
        inside = next((name for s, e, name in annotations if s <= mid < e),
                      None)
        out.append([f"inside {inside}" if inside else "between steps",
                    dur / 1e9])
    return out


def dump(path: str, limit: int = 40):
    """What a person looks at first: planes, lines, and the commonest event
    names with one event's stats each."""
    pd = load(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            agg, sample = {}, {}
            for e in evs:
                agg[e.name] = agg.get(e.name, 0) + e.duration_ns
                sample.setdefault(e.name, e)
            for name, ns in sorted(agg.items(), key=lambda kv: -kv[1])[
                    :limit if line.name == OPS_LINE else 8]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in sample[name].stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:80]!r}  {stats}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--kind", default=None)
    ap.add_argument("--dump", action="store_true")
    cli = ap.parse_args()
    if cli.dump:
        dump(cli.path)
        return
    if not cli.kind:
        ap.error("--kind is required (the device_kind the trace was taken "
                 "on, as in peaks.json)")
    json.dump(reduce_trace(cli.path, cli.kind), sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
