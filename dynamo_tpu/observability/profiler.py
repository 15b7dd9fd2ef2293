"""``jax.profiler`` hooks: the annotation gate + anomaly-triggered capture.

Two env-gated layers, both off by default:

- ``DYN_JAX_PROFILER=1`` (:func:`enabled`) makes the engine loop's phase
  clock (``flight.PhaseClock``) open a ``jax.profiler.TraceAnnotation`` at
  every transition, so device traces captured with
  ``jax.profiler.start_trace`` carry, on the clock of the device's ops,
  what the serving thread was doing: ``dynamo.idle``, ``dynamo.plan``,
  ``dynamo.blocked``, ``dynamo.build``, ``dynamo.put``,
  ``dynamo.dispatch``, ``dynamo.sample``, ``dynamo.device_wait``,
  ``dynamo.lag``, ``dynamo.commit``, ``dynamo.record``, ``dynamo.other``
  (``flight.PHASES``) — flat, never nested
  (docs/observability.md "The phase clock"). The annotations are a
  per-transition host-side cost the steady-state serving loop should not
  pay unasked.

- ``DYN_PROFILE_ON_ANOMALY=<dir>`` arms :class:`AnomalyProfiler`: when the
  flight recorder tags a step ``slow-step`` or ``compile-steady``, ONE
  bounded device-trace capture starts (the next ``DYN_PROFILE_STEPS``
  steps, default 8 — anomaly regimes persist: a preempt storm or a compile
  cliff is still burning when the tag lands), writes its artifact under
  the given directory, records the path on the triggering StepRecord
  (``dynctl timeline`` shows it), and then disarms for
  ``DYN_PROFILE_COOLDOWN_S`` (default 120) with a lifetime budget of
  ``DYN_PROFILE_MAX_CAPTURES`` (default 3) — an anomaly storm must never
  turn the profiler itself into the perf problem (docs/observability.md
  "Attribution").
"""

from __future__ import annotations

import logging
import os
import time
from typing import Callable, Optional

logger = logging.getLogger("dynamo.observability.profiler")

_enabled: bool | None = None


def enabled() -> bool:
    """Gate, computed once per process (the engine loop is hot)."""
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get(
            "DYN_JAX_PROFILER", "").lower() not in ("", "0", "false")
    return _enabled


def _reset_for_tests() -> None:
    global _enabled
    _enabled = None


# ------------------------------------------------- anomaly-triggered capture

#: flight tags that arm a capture (docs/observability.md): a slow step or
#: a steady-state compile is exactly the moment a device trace answers
#: "what was the accelerator doing"; preempt storms and bubbles are
#: host/scheduler phenomena the flight record itself already explains
TRIGGER_TAGS = frozenset({"slow-step", "compile-steady"})


class AnomalyProfiler:
    """Bounded ``jax.profiler`` capture armed by flight anomaly tags.

    Feed every appended :class:`~dynamo_tpu.observability.flight.StepRecord`
    through :meth:`on_record`. A record carrying a trigger tag starts a
    capture (unless cooling down or over the lifetime budget); the capture
    runs for ``steps`` further records, then stops and stamps the artifact
    path on the TRIGGERING record. ``start_fn``/``stop_fn`` default to
    ``jax.profiler.start_trace``/``stop_trace`` and are injectable so tests
    (and non-JAX hosts) exercise the arming logic without a real tracer.
    Never raises into the step loop — a broken profiler disables itself.
    """

    def __init__(self, base_dir: str, steps: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 max_captures: Optional[int] = None,
                 start_fn: Optional[Callable] = None,
                 stop_fn: Optional[Callable] = None,
                 now_fn=time.monotonic):
        def _env_num(name: str, default, kind):
            try:
                return kind(os.environ.get(name, "") or default)
            except ValueError:
                logger.warning("ignoring malformed %s", name)
                return default

        self.base_dir = base_dir
        self.steps = steps if steps is not None else _env_num(
            "DYN_PROFILE_STEPS", 8, int)
        self.cooldown_s = cooldown_s if cooldown_s is not None else \
            _env_num("DYN_PROFILE_COOLDOWN_S", 120.0, float)
        self.max_captures = max_captures if max_captures is not None else \
            _env_num("DYN_PROFILE_MAX_CAPTURES", 3, int)
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._now = now_fn
        self.captures = 0          # started (lifetime budget)
        self.capture_paths: list[str] = []
        self._last_capture_t = float("-inf")
        self._active: Optional[dict] = None  # {rec, remaining, path}
        self._broken = False

    @classmethod
    def from_env(cls) -> Optional["AnomalyProfiler"]:
        """None unless ``DYN_PROFILE_ON_ANOMALY`` names a directory."""
        base = os.environ.get("DYN_PROFILE_ON_ANOMALY")
        return cls(base) if base else None

    # -- capture plumbing --------------------------------------------------

    def _start(self, path: str) -> None:
        if self._start_fn is not None:
            self._start_fn(path)
            return
        import jax.profiler
        jax.profiler.start_trace(path)

    def _stop(self) -> None:
        if self._stop_fn is not None:
            self._stop_fn()
            return
        import jax.profiler
        jax.profiler.stop_trace()

    def on_record(self, rec) -> None:
        """Called with each appended StepRecord (engine step loop)."""
        if self._broken or rec is None:
            return
        try:
            if self._active is not None:
                self._active["remaining"] -= 1
                if self._active["remaining"] <= 0:
                    self._finish()
                return
            if not TRIGGER_TAGS.intersection(rec.tags):
                return
            now = self._now()
            if self.captures >= self.max_captures:
                return
            if now - self._last_capture_t < self.cooldown_s:
                return
            path = os.path.join(
                self.base_dir, f"anomaly-{self.captures + 1}-seq{rec.seq}")
            os.makedirs(path, exist_ok=True)
            self._start(path)
            self.captures += 1
            self._last_capture_t = now
            self._active = {"rec": rec, "remaining": max(1, self.steps),
                            "path": path}
            # stamp the TRIGGERING record so `dynctl timeline` and the
            # attribution evidence list link the anomaly to its trace
            rec.profile_path = path
            self.capture_paths.append(path)
            logger.warning(
                "anomaly %s at step %d armed device-trace capture → %s "
                "(%d/%d captures, cooldown %.0fs)",
                ",".join(rec.tags), rec.seq, path, self.captures,
                self.max_captures, self.cooldown_s)
        except Exception:
            logger.exception("anomaly profiler failed; disabling")
            self._broken = True
            self._active = None

    def _finish(self) -> None:
        active, self._active = self._active, None
        try:
            self._stop()
            logger.info("anomaly capture complete: %s", active["path"])
        except Exception:
            logger.exception("anomaly profiler stop failed; disabling")
            self._broken = True

    def close(self) -> None:
        """Stop a capture left open (engine shutdown mid-capture)."""
        if self._active is not None:
            self._finish()
