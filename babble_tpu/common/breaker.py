"""Circuit breaker: stop hammering a failing dependency, probe it back.

Classic three-state machine (closed → open → half-open → closed):

- **closed**: calls flow. Failures are timestamped; when ``threshold``
  failures land within ``window_s``, the breaker opens.
- **open**: calls are refused (``allow()`` is False) for ``cooldown_s``,
  so a dying dependency isn't paid for on every call.
- **half-open**: after the cooldown, exactly ONE probe call is admitted.
  Success closes the breaker (failure history cleared); failure re-opens
  it for another cooldown.

Used by hashgraph/accel.py to gate the device sweep path: a flapping
accelerator (runtime errors, OOMs) degrades to the oracle for a cooldown
instead of eating a dispatch failure per flush, and — unlike a sticky
kill-switch — the probe sweep re-enables the device once it recovers.

``clock`` is injectable so tests drive the state machine without
sleeping. Thread-safe: gossip threads and the readback reader may race
record_* against allow().
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class CircuitBreaker:
    def __init__(
        self,
        threshold: int = 5,
        window_s: float = 30.0,
        cooldown_s: float = 15.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        if threshold < 1:
            raise ValueError("threshold must be >= 1")
        self.threshold = threshold
        self.window_s = window_s
        self.cooldown_s = cooldown_s
        # Accept either a bare monotonic callable or a common.clock.Clock
        # object (the node hands its Clock through, so simulated breakers
        # trip and cool down on virtual time).
        self._clock = getattr(clock, "monotonic", clock)
        self._lock = threading.Lock()
        self._state = CLOSED
        self._failures: List[float] = []  # timestamps inside the window
        self._opened_at = 0.0
        self._probe_out = False  # half-open: one probe admitted at a time
        # counters surfaced through stats()
        self.opens = 0  # closed/half-open → open transitions
        self.probes = 0  # probe calls admitted while half-open
        self.skips = 0  # calls refused while open
        self.failures_total = 0
        self.successes_total = 0

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self) -> bool:
        """True when a call may proceed. While open, flips to half-open
        once the cooldown elapses and admits a single probe."""
        now = self._clock()
        with self._lock:
            if self._state == CLOSED:
                return True
            if self._state == OPEN:
                if now - self._opened_at < self.cooldown_s:
                    self.skips += 1
                    return False
                self._state = HALF_OPEN
                self._probe_out = False
            # half-open: admit one probe; refuse the rest until it reports
            if self._probe_out:
                self.skips += 1
                return False
            self._probe_out = True
            self.probes += 1
            return True

    def cancel(self) -> None:
        """The admitted call never actually reached the dependency (e.g.
        kernels still compiling, admission slot lost): release the probe
        without treating it as an outcome."""
        with self._lock:
            self._probe_out = False

    def record_success(self) -> None:
        with self._lock:
            self.successes_total += 1
            if self._state == OPEN:
                # late success from a call admitted before the trip (e.g.
                # an in-flight readback landing after the Nth failure):
                # the cooldown still stands — only a half-open probe may
                # re-close the breaker
                return
            self._failures.clear()
            self._probe_out = False
            self._state = CLOSED

    def record_failure(self) -> None:
        now = self._clock()
        with self._lock:
            self.failures_total += 1
            if self._state == HALF_OPEN:
                # the probe failed: straight back to open, fresh cooldown
                self._open(now)
                return
            if self._state == OPEN:
                # late failure from a call admitted before the open (e.g.
                # an in-flight readback landing after the breaker tripped)
                return
            self._failures.append(now)
            cutoff = now - self.window_s
            self._failures = [t for t in self._failures if t >= cutoff]
            if len(self._failures) >= self.threshold:
                self._open(now)

    def _open(self, now: float) -> None:
        self._state = OPEN
        self._opened_at = now
        self._failures.clear()
        self._probe_out = False
        self.opens += 1

    def stats(self, prefix: str = "breaker_") -> dict:
        with self._lock:
            return {
                f"{prefix}state": self._state,
                f"{prefix}open": self.opens,
                f"{prefix}probes": self.probes,
                f"{prefix}skips": self.skips,
                f"{prefix}failures": self.failures_total,
            }
