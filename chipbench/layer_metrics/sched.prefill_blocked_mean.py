"""Admitted sequences with prompt tokens left that a step's plan gave no
chunk, mean over the window's ``ragged`` steps: the queue inside ``running``,
which ``sched.waiting_mean`` does not see. Read from the flight field
``prefill_blocked``; a tree whose records lack the field is read as what its
records leave over, ``running − decode_rows − prefill_chunks −
starved_decode`` (``running`` is taken after the step, so rows that ended in
it are missed and the fallback reads a little low)."""
SOURCE = "flight"


def compute(src):
    from sources import mean

    steps = [s for s in src.flight if s.get("kind") == "ragged"]
    if any("prefill_blocked" in s for s in steps):
        return mean([s.get("prefill_blocked", 0) for s in steps])
    return mean([max(0, s.get("running", 0) - s.get("decode_rows", 0)
                     - s.get("prefill_chunks", 0)
                     - s.get("starved_decode", 0)) for s in steps])
