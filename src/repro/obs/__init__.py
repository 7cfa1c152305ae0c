"""Runtime observability — DESIGN.md §10.

Four pieces, split by where they run:

  * ``telemetry``  — in-step device health metrics (per-emb-group norms,
    nonfinite counts with leaf attribution, lookup occupancy, routing
    skew) that ride the train step's existing launch as extra entries in
    the returned ``metrics`` dict.  Zero extra dispatches — asserted by
    the ``train_step_telemetry`` audit spec.
  * ``pump``       — the host-side async metrics pump: a ring of
    in-flight device metric trees drained N steps late, so reading
    metrics never forces the dispatch pipeline to sync.
  * ``runlog``     — schema-versioned JSONL run log (manifest + typed
    events: step records, trigger evaluations, transitions, checkpoint
    save/restore, fault fires, serve latency) with restart-safe
    append-and-dedupe semantics, plus the fixed-bucket
    ``LatencyHistogram`` the serve engine feeds.
  * ``trace``      — host spans (``jax.profiler.TraceAnnotation``, with
    integer args as event stats) on the training loop's phases and the
    sketch tracker's fold thread, listed by thread in its docstring; no
    ``jax.named_scope`` on host phases (the transition opens its own).
    Also the opt-in ``ProfileWindow`` profiler-trace dump.

``python -m repro.obs summarize RUN.jsonl`` renders a run log (p50/p99
step time, loss curve, trigger/transition timeline, shard balance).
The CLI (``summary``, ``runlog``) is importable without jax — device
imports stay behind this lazy ``__getattr__``.
"""
from repro.obs.runlog import SCHEMA_VERSION, LatencyHistogram, RunLog

_LAZY = {
    "TelemetryConfig": "repro.obs.telemetry",
    "telemetry_metrics": "repro.obs.telemetry",
    "telemetry_labels": "repro.obs.telemetry",
    "MetricsPump": "repro.obs.pump",
    "span": "repro.obs.trace",
    "ProfileWindow": "repro.obs.trace",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "SCHEMA_VERSION",
    "RunLog",
    "LatencyHistogram",
    "TelemetryConfig",
    "telemetry_metrics",
    "telemetry_labels",
    "MetricsPump",
    "span",
    "ProfileWindow",
]
