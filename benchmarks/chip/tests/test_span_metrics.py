"""The readers of the program's sketch-tracker and loop spans on a
synthesised profiler trace with known answers."""
import pytest
from jax.profiler import ProfileData

import bench
from harness import trace as tr
from test_trace_reduce import _plane

NAMES = {1: tr.WINDOW_SPAN, 2: "next-batch", 3: "dispatch", 4: "sketch-fold",
         5: "sketch-enqueue-wait", 6: "sketch-window-close", 7: "sketch-flush",
         8: "metrics-pump", 9: "fold-batch", 10: "fold-fetch", 11: "fold-cells",
         12: "fold-heads"}
# the loop thread, in ms: the window [10,110), two steps; the first waits on
# the fold queue over [22,30) and closes a sketch window over [32,48)
LOOP = [(1, 10, 100), (2, 12, 3), (3, 15, 5), (4, 20, 30), (5, 22, 8), (6, 32, 16),
        (7, 33, 14), (8, 50, 5), (2, 55, 3), (3, 58, 2), (4, 60, 2), (8, 62, 2)]
# the fold thread: a batch that straddles the window's start, then two inside
FOLD = [(9, 5, 20), (10, 5, 7), (11, 12, 3), (12, 15, 9),
        (9, 30, 20), (10, 30, 4), (11, 34, 2), (12, 36, 12),
        (9, 60, 20), (10, 60, 10), (11, 70, 2), (12, 72, 7)]
# device ops at [20,30) and [60,70)
DEVICE = _plane(1, "/device:TPU:0", [("XLA Ops", [(1, 20, 10), (1, 60, 10)]),
                                     ("XLA Modules", [(2, 20, 10), (2, 60, 10)])],
                {1: "%fusion.1", 2: "jit_train_step(1)"})


def _trace(*, fold=True, device=True, names=NAMES):
    lines = [("python3", LOOP)] + ([("python3", FOLD)] if fold else [])
    host = _plane(2, "/host:CPU", lines, names)
    return tr.load(ProfileData.from_text_proto((DEVICE if device else "") + host))


def _read(name, trace, mode="train"):
    reader = bench.load_module(bench.HERE / "metrics" / f"{name}.py")
    return reader.read({"mode": mode, "trace": trace, "run": {"steps": 2}})


KNOWN = [
    ("sketch_enqueue_wait_ms.train", 8 / 2),
    ("sketch_window_close_ms.train", 16 / 2),
    # [10,25) of the straddling batch, then [30,50) and [60,80)
    ("fold_busy_share.train", 55.0),
    # per batch that starts in the window: none of the straddling one's phases
    ("fold_fetch_ms.train", (4 + 10) / 2),
    ("fold_cells_ms.train", (2 + 2) / 2),
    ("fold_heads_ms.train", (12 + 7) / 2),
    # idle [10,12) and [70,110): no loop span is open
    ("device_idle_no_span_share.train", 42.0),
]


@pytest.mark.parametrize("name,want", KNOWN)
def test_reader_known_answer(name, want):
    assert _read(name, _trace()) == pytest.approx(want)


def test_fold_thread_spans_leave_the_idle_attribution_alone():
    # the fold thread's spans overlap the loop's everywhere; they are not
    # among the names the reduction is given, so the share is the same
    # without them
    alone = _read("device_idle_no_span_share.train", _trace(fold=False))
    assert alone == pytest.approx(_read("device_idle_no_span_share.train", _trace()))
    # the parent's reduction leaves next-batch and metrics-pump as no span
    red = tr.reduce(_trace(), tr.SPAN_NAMES)
    assert red.idle_gaps[tr.NO_SPAN] == pytest.approx(0.053)


def test_no_device_plane_reads_nothing():
    assert _read("device_idle_no_span_share.train", _trace(device=False)) is None


@pytest.mark.parametrize("name", [n for n, _ in KNOWN])
def test_program_without_the_spans_or_no_trace_reads_nothing(name):
    # the parent's program: the harness's spans only
    old = {k: v for k, v in NAMES.items() if v in (tr.WINDOW_SPAN, "dispatch", "sketch-fold")}
    loop = [e for e in LOOP if e[0] in old]
    host = _plane(2, "/host:CPU", [("python3", loop)], old)
    parent = tr.load(ProfileData.from_text_proto(DEVICE + host))
    assert _read(name, parent) is None
    assert _read(name, None) is None
    assert _read(name, _trace(), mode="serve") is None
