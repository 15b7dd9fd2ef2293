"""What the largest mixed step of the window costs the loop, whole window:
the median ``period_ms`` of the ``ragged`` records whose token bucket
(``decode_rows + chunk_tokens + padded_tokens``) is the largest one seen and
that held no ``idle``; ``None`` under five such records."""
SOURCE = "flight"
MIN_RECORDS = 5


def compute(src):
    from sources import median

    def bucket(s):
        return (s.get("decode_rows", 0) + s.get("chunk_tokens", 0)
                + s.get("padded_tokens", 0))

    ragged = [s for s in src.flight
              if s.get("kind") == "ragged" and s.get("period_ms")]
    top = max(map(bucket, ragged), default=0)
    periods = [s["period_ms"] for s in ragged
               if bucket(s) == top and not s["phases"].get("idle")]
    return median(periods) if len(periods) >= MIN_RECORDS else None
