"""What a per-layer reader (``layer_metrics/<name>.py``) is handed: the raw
material of one run, and the two helpers most readers need. A reader returns
a number, or ``None`` when its source has nothing (the harness then leaves
the metric out of the line)."""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Optional

from fleet import metric_samples


@dataclass
class Sources:
    client: dict                      # loadgen.summarize() of the run
    flight: list                      # StepRecord dicts inside the window
    worker_metrics: tuple             # (/metrics at window start, at end)
    frontend_metrics: tuple
    log: str                          # the worker's log
    facts: dict                       # engine built: {...} + ready times
    trace: Optional[dict] = None      # trace_reduce.reduce_trace(), traced runs

    def delta(self, which: str, name: str) -> dict:
        """{labels: after − before} of one Prometheus family; ``which`` is
        ``worker`` or ``frontend``."""
        before, after = getattr(self, which + "_metrics")
        b, a = metric_samples(before, name), metric_samples(after, name)
        return {k: v - b.get(k, 0.0) for k, v in a.items()}

    def delta_sum(self, which: str, name: str) -> Optional[float]:
        d = self.delta(which, name)
        return sum(d.values()) if d else None

    def device(self, which: str = "first_device") -> Optional[dict]:
        """One device's entry of the reduced trace (``first_device`` or
        ``worst_idle_device``)."""
        if not self.trace:
            return None
        return self.trace["devices"][self.trace[which]]


def median(xs: list) -> Optional[float]:
    return statistics.median(xs) if xs else None


def mean(xs: list) -> Optional[float]:
    return statistics.fmean(xs) if xs else None
