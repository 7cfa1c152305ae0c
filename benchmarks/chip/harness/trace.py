"""Profiler window and the reduction of its trace to device metrics.

A traced run opens the JAX profiler over the measured window and marks the
window with a host span (``WINDOW_SPAN``).  The reduction reads the
``.xplane.pb`` the profiler writes, with nothing but JAX:

* device operations are the events of each device plane's ``XLA Ops``
  line, and programs the events of its ``XLA Modules`` line;
* busy time is the union of the operation intervals inside the window,
  averaged over the devices used; the idle share is 1 - busy / window;
* each idle gap, split where a host span opens or closes inside it, is
  attributed piece by piece to the innermost host span open there (the
  program's own spans and the harness's spans around its calls into the
  program), or to ``(no span)``.

Host and device events share the trace's clock: the profiler puts both on
the host's time line.  Each host event keeps its thread and its args (a
span's keyword arguments), for readers that count what a span records; the
reduction reads neither.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import shutil
import tempfile
import warnings

WINDOW_SPAN = "bench-window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"
#: host spans an idle gap can be attributed to: the program's own
#: (``train/loop.py``) and the harness's around its calls into the program
SPAN_NAMES = ("dispatch", "sketch-fold", "transition", "checkpoint",
              "gen-submit", "engine-step", "idle-wait")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    #: a host event's thread: the index of its line among the host lines
    #: (one line a thread; threads' names repeat, so the name is no key)
    thread: int | None = None
    #: the profiler's own host event, whose stats ``args`` reads on demand
    raw: object = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def args(self) -> dict | None:
        """A host event's args (the span's keyword arguments, or what it set
        through ``set_metadata``), by name; None where it has none.  Read
        when asked for: a traced run holds far more events than any reader
        asks the args of."""
        if self.raw is None:
            return None
        with warnings.catch_warnings():  # each read of ``stats`` warns
            warnings.simplefilter("ignore", DeprecationWarning)
            return dict(self.raw.stats) or None


@dataclasses.dataclass
class Trace:
    """The events a reduction needs: per device, its ops and programs, and
    the host spans with their threads and args."""

    ops: dict[str, list[Event]]
    modules: dict[str, list[Event]]
    host: list[Event]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(profile_data) -> Trace:
    """A ``jax.profiler.ProfileData`` -> :class:`Trace`."""
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    host: list[Event] = []
    n_threads = 0
    for plane in profile_data.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(e.name, e.start_ns, e.duration_ns) for e in line.events]
                (ops if line.name == OPS_LINE else modules).setdefault(
                    plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = n_threads
                n_threads += 1
                host.extend(Event(e.name, e.start_ns, e.duration_ns, thread, e)
                            for e in line.events)
    return Trace(ops, modules, host)


def load_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    return load(ProfileData.from_file(path))


def window(trace: Trace) -> tuple[float, float] | None:
    ws = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not ws:
        return None
    return ws[0].start_ns, ws[0].end_ns


def _clip(evs, lo, hi):
    for e in evs:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            yield s, t


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over devices
    op_s: dict[str, float]  # summed over devices, by op name
    module_s: dict[str, float]  # summed over devices, by program name
    module_n: dict[str, int]
    idle_gaps: dict[str, float]  # idle seconds by the host span open in them
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, *needles: str) -> float:
        """Device seconds of the ops whose name contains any needle."""
        return sum(s for n, s in self.op_s.items() if any(x in n for x in needles))

    def breakdown(self, k: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _span_at(spans, starts, t):
    """Innermost host span open at ``t``: the latest-starting one that
    contains it (spans sorted by start; a few dozen back suffice, since
    the spans of one thread do not overlap but by nesting)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 64, -1), -1):
        if spans[j].end_ns > t:
            return spans[j].name
    return NO_SPAN


def reduce(trace: Trace, span_names) -> Reduction | None:
    """Reduce the traced window; None when the trace holds no window or no
    device operation."""
    w = window(trace)
    if w is None or not any(trace.ops.values()):
        return None
    lo, hi = w
    busy, op_s = [], collections.Counter()
    module_s, module_n = collections.Counter(), collections.Counter()
    gaps = collections.Counter()
    spans = [e for e in trace.host if e.name in set(span_names)]
    spans.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    bounds = sorted({x for e in spans for x in (e.start_ns, e.end_ns)})
    devices = sorted(trace.ops)
    for dev in devices:
        evs = trace.ops[dev]
        for e in evs:
            s = min(e.end_ns, hi) - max(e.start_ns, lo)
            if s > 0:
                op_s[e.name] += s * 1e-9
        merged = union(_clip(evs, lo, hi))
        busy.append(sum(t - s for s, t in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            # split the gap where a host span opens or closes inside it
            cuts = bounds[bisect.bisect_right(bounds, s):bisect.bisect_left(bounds, t)]
            pts = [s, *cuts, t]
            for a, b in zip(pts[:-1], pts[1:]):
                if b > a:
                    gaps[_span_at(spans, starts, (a + b) / 2)] += (b - a) * 1e-9 / len(devices)
        for e in trace.modules.get(dev, []):
            if lo <= e.start_ns < hi:
                module_s[e.name] += e.dur_ns * 1e-9
                module_n[e.name] += 1
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(devices) * 1e-9,
        op_s=dict(op_s), module_s=dict(module_s), module_n=dict(module_n),
        idle_gaps=dict(gaps), n_devices=len(devices),
    )


_COMPILES = [0]
_WATCHING = [False]


def compiles() -> int:
    """Programs compiled or loaded from the compile cache so far in this
    process (a window should add none)."""
    if not _WATCHING[0]:
        import jax

        def count(event, duration, **kw):
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/compilation_cache/cache_retrieval_time_sec"):
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
        _WATCHING[0] = True
    return _COMPILES[0]


class Profile:
    """``with Profile(True) as p: ...`` traces the block into a temporary
    directory under ``TMPDIR``; ``p.trace`` is the loaded :class:`Trace`
    (None when off).  The directory is deleted once read."""

    def __init__(self, on: bool):
        self.on = on
        self.trace: Trace | None = None
        self._dir = None

    def __enter__(self):
        if self.on:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # spans only: no per-call Python events
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if not self.on:
            return False
        import jax

        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                                  recursive=True)
                if paths:
                    self.trace = load_file(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
