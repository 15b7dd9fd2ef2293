"""Share of the recurrent-state slots held by running sequences
(``state_slots_used`` of the flight records over the worker's
``state_slots``), mean over the window's steps. A model without state
layers has neither: nothing to read."""
SOURCE = "flight"


def compute(src):
    from sources import mean

    slots = (src.facts or {}).get("state_slots")
    if not slots or not src.flight:
        return None
    return 100.0 * mean([s.get("state_slots_used", 0)
                         for s in src.flight]) / slots
