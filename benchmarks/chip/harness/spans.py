"""The program's own host spans in a traced window, as the per-layer
readers of the sketch tracker and the training loop see them.

A span counts where it starts inside the ``bench-window`` span, and a
phase of the fold where its ``fold-batch`` does; the host lines are
searched alike, so the loop thread's spans and those of the
tracker's fold thread are both found.  A window that holds no
``fold-batch`` (or, for the loop's spans, no ``next-batch``) comes from a
program without these spans: its readers report nothing.
"""
from __future__ import annotations

import bisect

from harness.trace import union, window

#: the training loop's spans that nest inside or beside ``SPAN_NAMES``' on
#: the loop thread (the fold thread's spans are never among them)
LOOP_SPANS = ("next-batch", "metrics-pump", "sketch-enqueue-wait",
              "sketch-window-close", "sketch-flush")


def in_window(trace, name: str) -> list:
    w = window(trace)
    if w is None:
        return []
    return [e for e in trace.host if e.name == name and w[0] <= e.start_ns < w[1]]


def _train_trace(ctx):
    return ctx["trace"] if ctx["mode"] == "train" else None


def ms_per_step(ctx, name: str) -> float | None:
    """Total length of the ``name`` spans per window step, in ms."""
    tr = _train_trace(ctx)
    if tr is None or not in_window(tr, "fold-batch"):
        return None
    return 1e-6 * sum(e.dur_ns for e in in_window(tr, name)) / ctx["run"]["steps"]


def ms_per_fold(ctx, name: str) -> float | None:
    """Total length of the ``name`` phases of the ``fold-batch`` spans that
    start in the window, per such batch, in ms."""
    tr = _train_trace(ctx)
    folds = sorted(in_window(tr, "fold-batch") if tr is not None else [],
                   key=lambda e: e.start_ns)
    if not folds:
        return None
    starts = [e.start_ns for e in folds]
    total = 0.0
    for e in tr.host:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if e.name == name and i >= 0 and e.end_ns <= folds[i].end_ns:
            total += e.dur_ns
    return 1e-6 * total / len(folds)


def busy_share(ctx, name: str) -> float | None:
    """The union of the ``name`` spans, clipped to the window, over the
    window, in %."""
    tr = _train_trace(ctx)
    if tr is None or not in_window(tr, name):
        return None
    lo, hi = window(tr)
    clipped = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in tr.host if e.name == name]
    busy = sum(t - s for s, t in union((s, t) for s, t in clipped if t > s))
    return 100.0 * busy / (hi - lo)
