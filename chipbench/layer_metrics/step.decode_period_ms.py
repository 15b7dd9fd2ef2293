"""What a pipelined decode step costs the loop: the median ``period_ms`` (the
serving thread's time from one record to the next) of ``decode_pipe`` records
that held no ``idle``. ``step.decode_host_ms`` beside it is a LATENCY through
the depth-2 pipe, one to two of these."""
SOURCE = "flight"


def compute(src):
    from sources import median

    return median([s["period_ms"] for s in src.flight
                   if s.get("kind") == "decode_pipe" and s.get("period_ms")
                   and not s["phases"].get("idle")])
