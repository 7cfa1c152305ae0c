"""Trace spans and the opt-in profiler window.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: a host-side
interval in a captured profiler trace, on the same clock as the device's
operations, with integer ``args`` kept as the event's stats.  Counts known
only when the phase ends go in through ``set_metadata`` on the object the
``with`` binds.  Off-trace a span costs well under a microsecond.  Host
phases carry no ``jax.named_scope``: it names only ops traced inside it,
and host phases trace none.  The one phase that traces device work under
its name, the clustering transition, opens ``jax.named_scope`` itself
(``train/loop.py``).

The spans that exist, by thread:

    loop thread (``Trainer.run``, ``train/loop.py``)
      next-batch            ``next(data_iter)`` and the accumulation reshape
      translate             host pointer translation, inside next-batch
                            when the pipeline translates (data/translate.py)
      dispatch              the jitted train step call
      sketch-fold           tracker observe (stream/tracker.py)
        sketch-enqueue-wait a submit blocked on the fold queue
                            (stream/device.py); arg ``depth``, the queue
                            depth when the block started
        sketch-window-close the window close: flush, summary, decay
          sketch-flush      the wait for the fold thread to drain; also
                            wherever the tracker's ``flush()`` is called
      metrics-pump          the pump's push and every flush (obs/pump.py)
      transition            the clustering transition (Alg. 3)
      checkpoint            async checkpoint save enqueue
    fold thread (``SketchFrequencyTracker._fold``, with ``async_fold``)
      fold-batch            one folded batch
        fold-fetch          the device-to-host copy of the cell delta
        fold-cells          the count-min cell adds, every tracked feature
        fold-heads          the head and ring bookkeeping, every tracked
                            feature; args ``candidates``, ``admitted``,
                            ``evicted`` and ``rebuilds``: SpaceSaving's ids
                            offered a slot, admissions, evictions and full
                            rebuilds of its residency index

The fold thread's spans run on the loop thread instead when the tracker
folds synchronously.  No fold-thread span shares a name with a loop-thread
span: a reduction that attributes time to the innermost open span assumes
the spans it is given nest, as one thread's do.

``ProfileWindow`` dumps a ``jax.profiler`` trace directory for a
half-open step window [start, stop) — pass
``Trainer(profile_steps=(start, stop), profile_dir=...)`` and view the
result in TensorBoard/XProf.  One window per process: profiling is a
heavy, explicitly-requested act, not an always-on mode.
"""
from __future__ import annotations

import dataclasses

import jax


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with integer ``args`` as its stats; use
    it as a context manager."""
    return jax.profiler.TraceAnnotation(name, **args)


@dataclasses.dataclass
class ProfileWindow:
    """Opt-in [start, stop) profiler capture, driven by step number."""

    start: int
    stop: int
    log_dir: str
    active: bool = False
    done: bool = False

    def __post_init__(self):
        assert self.start < self.stop, "profile window must be non-empty"

    def observe(self, step: int) -> None:
        """Call once per loop iteration with the step about to run."""
        if self.active and step >= self.stop:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
        if not self.done and not self.active and self.start <= step < self.stop:
            jax.profiler.start_trace(self.log_dir)
            self.active = True

    def close(self) -> None:
        """Stop a still-open capture (end of run / exception path)."""
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
