"""The async metrics pump — host-side metric draining that never stalls
the dispatch pipeline.

jax dispatch is asynchronous: the step call returns futures and the
device keeps executing while the host prepares the next batch.  Reading
a metric value (``float(metrics["loss"])``) blocks until THAT step
finishes — done every step, it serializes host and device and the
measured step time quietly includes the sync (the exact bug the old
``Trainer.run`` had).

``MetricsPump`` holds a ring of in-flight device metric trees and only
``device_get``s an entry once it is ``lag`` steps behind the dispatch
front — by then the values are already materialized and the transfer is
a no-wait copy.  Host-visible effects:

  * ``history`` — bounded deque (``maxlen``) of per-step records: python
    floats for scalars, numpy arrays for telemetry vectors.
  * ``sink``    — optional callback per drained record (the Trainer
    wires ``RunLog`` step events through this).

``flush()`` drains everything in flight — the explicit sync point for
tests, checkpoint boundaries, and end-of-run (records are exact and
complete after a flush; only their *timing* is late).
"""
from __future__ import annotations

from collections import deque
from typing import Callable

import jax
import numpy as np

from repro.obs.trace import span


def _to_host(tree):
    """device tree -> record leaves: 0-d values become python floats,
    vectors become numpy arrays (json-ready via RunLog's encoder)."""
    host = jax.device_get(tree)

    def conv(x):
        arr = np.asarray(x)
        return float(arr) if arr.ndim == 0 else arr

    return jax.tree.map(conv, host)


class MetricsPump:
    """Ring of (step, device metric tree) drained ``lag`` steps late."""

    def __init__(
        self,
        *,
        lag: int = 8,
        maxlen: int | None = 10_000,
        sink: Callable[[dict], None] | None = None,
    ):
        self.lag = max(0, int(lag))
        self.history: deque[dict] = deque(maxlen=maxlen)
        self.sink = sink
        self._ring: deque = deque()

    def __len__(self) -> int:  # records still in flight
        return len(self._ring)

    def push(self, step: int, metrics, *, extra: dict | None = None) -> None:
        """Enqueue one step's device metrics; drains whatever fell
        ``lag`` steps behind.  ``extra`` carries host-side fields (dt)
        that ride the record without touching the device."""
        with span("metrics-pump"):
            self._ring.append((step, metrics, extra))
            while len(self._ring) > self.lag:
                self._drain_one()

    def _drain_one(self) -> None:
        step, metrics, extra = self._ring.popleft()
        record = _to_host(metrics)
        record["step"] = int(step)
        if extra:
            record.update(extra)
        self.history.append(record)
        if self.sink is not None:
            self.sink(record)

    def flush(self) -> None:
        """Drain every in-flight record (blocks until the device catches
        up — the documented sync point for tests and checkpoints)."""
        with span("metrics-pump"):
            while self._ring:
                self._drain_one()
