"""The state update's share of its roofline: for the launches of the op
``mamba2_decode_update_l<first>x<layers>_<program>`` among the ten costliest
ops (all a reader is shown), the least seconds the chip's peaks allow for
the updates THEY ran — ``ssm_costs`` of the rows (flight field
``state_rows_decode``) of the traced slice's steps of that program
(``state_program``), times the run's layers — over their self time. A
launch that is not listed leaves both its work and its time out."""
SOURCE = "trace"
NAME = "mamba2_decode_update_l"


def compute(src):
    import re

    import ssm_costs
    from trace_reduce import peaks_for

    facts, tr = src.facts or {}, src.trace
    if not tr or not facts.get("mamba") or "asked" not in tr:
        return None
    on, off = tr["asked"]["on_epoch"], tr["asked"]["stop_epoch"]
    rows_of = {}
    for s in src.flight:
        if on <= s.get("t", 0) <= off and s.get("state_program"):
            rows_of[s["state_program"]] = rows_of.get(
                s["state_program"], 0) + s.get("state_rows_decode", 0)
    seconds, rows = 0.0, 0
    for label, self_s in tr["breakdown"]["device_ops"]:
        m = re.match(r"%?" + NAME + r"\d+x(\d+)_([dm]\d+)",
                     label.split(" = ", 1)[0])
        if m and rows_of.get(m.group(2)):
            seconds += self_s
            rows += int(m.group(1)) * rows_of[m.group(2)]
    if not rows or not seconds:
        return None
    H, P, N = (facts["mamba"][k] for k in ("heads", "d_head", "d_state"))
    peaks = peaks_for(tr["kind"])
    least = ssm_costs.roofline_seconds(
        ssm_costs.update_ops(rows, H, P, N),
        ssm_costs.update_bytes(rows, H, P, N),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
