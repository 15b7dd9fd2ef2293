"""Not a metric: what the ``loop.*_share`` readers share. The engine loop's
phase clock (``dynamo_tpu/observability/flight.py`` ``PhaseClock``) puts on
every flight record ``period_ms``, the serving thread's time since the record
before it, and ``phases``, that time by what the thread was doing."""


def share(flight: list, *phases: str):
    """Σ of these phases over Σ ``period_ms`` of ALL the window's records,
    ``empty`` ones included, in %. ``None`` where no record carries a period:
    a tree without the phase clock."""
    period = sum(s.get("period_ms", 0.0) for s in flight)
    if not period:
        return None
    return 100.0 * sum(s.get("phases", {}).get(p, 0.0)
                       for s in flight for p in phases) / period
