"""Host spans of the training loop and of the sketch tracker's fold thread,
read back from a profiler trace captured on the CPU (spans only, no Python
tracer): the fold's phases nest inside one ``fold-batch`` on a thread of
their own, the loop's new spans appear once per step or per event, and a
submit that blocks on the fold queue is the only one that opens
``sketch-enqueue-wait``."""
import dataclasses
import glob
import queue
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import dlrm_criteo
from repro.data import ClickstreamConfig, clickstream_batches
from repro.models import dlrm
from repro.optim import sgd
from repro.stream import SketchFrequencyTracker, StreamConfig, make_step_cell_counter
from repro.stream.device import AsyncFolder, make_cell_counter
from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

STEPS, WINDOW = 8, 4
FOLD_PHASES = ("fold-fetch", "fold-cells", "fold-heads")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict


def _capture(tmp_path, fn) -> list[list[Span]]:
    """Run ``fn`` under the profiler; -> the host lines (one per thread),
    each a list of its events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with warnings.catch_warnings():  # jaxlib's stats type warns as it is built
        warnings.simplefilter("ignore", DeprecationWarning)
        return [
            [Span(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for e in line.events]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
        ]


def _named(lines, name):
    return [s for line in lines for s in line if s.name == name]


def _line_of(lines, name) -> int:
    (i,) = {i for i, line in enumerate(lines) for s in line if s.name == name}
    return i


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A Trainer whose step carries the sketch delta, with an async-fold
    tracker of window 4, traced over one ``run(8)`` and the flush after."""
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    tracker = dlrm.make_id_tracker(
        cfg, dlrm_criteo.reduced_stream(window=WINDOW, async_fold=True))
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static,
                           sketch_fn=make_step_cell_counter(tracker))
    data = clickstream_batches(ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=0), 32)
    trainer = Trainer(jax.jit(step, donate_argnums=(0,)), init_state(params, opt, dyn),
                      static, data, id_tracker=tracker)
    trainer.run(1)  # compiles outside the trace
    tracker.flush()
    seen = tracker.batches_seen

    def window():
        trainer.run(STEPS)
        tracker.flush()

    lines = _capture(tmp_path_factory.mktemp("trace"), window)
    closed = tracker.batches_seen // WINDOW - seen // WINDOW
    return lines, closed


def test_fold_phases_nest_in_fold_batch_on_their_own_thread(trained):
    lines, _ = trained
    fold_line = _line_of(lines, "fold-batch")
    assert fold_line != _line_of(lines, "dispatch")
    line = lines[fold_line]
    batches = [s for s in line if s.name == "fold-batch"]
    assert len(batches) == STEPS
    for b in batches:
        inner = sorted((s for s in line if s.name in FOLD_PHASES
                        and b.start <= s.start and s.end <= b.end), key=lambda s: s.start)
        assert [s.name for s in inner] == list(FOLD_PHASES)
        assert all(x.end <= y.start for x, y in zip(inner, inner[1:]))
    heads = _named(lines, "fold-heads")
    assert all(set(s.stats) == {"candidates", "admitted", "evicted", "rebuilds"}
               for s in heads)
    assert sum(s.stats["admitted"] for s in heads) > 0
    assert all(s.stats["candidates"] >= s.stats["admitted"] for s in heads)
    assert sum(s.stats["rebuilds"] for s in heads) == 0  # updated in place


def test_one_window_close_per_closed_window(trained):
    lines, closed = trained
    closes = _named(lines, "sketch-window-close")
    assert closed == 2 and len(closes) == closed
    flushes = _named(lines, "sketch-flush")
    for c in closes:  # the barrier is part of the close
        assert any(c.start <= f.start and f.end <= c.end for f in flushes)


def test_loop_spans_once_per_step(trained):
    lines, _ = trained
    assert len(_named(lines, "dispatch")) == STEPS
    assert len(_named(lines, "next-batch")) == STEPS
    # a push per step and the flush that ends the run() call
    assert len(_named(lines, "metrics-pump")) == STEPS + 1
    assert not _named(lines, "sketch-enqueue-wait")  # the queue of 64 never filled


def test_enqueue_wait_spans_count_blocked_submits(tmp_path):
    """A stalled fold worker behind a queue of 2: every submit that finds
    the queue full opens one ``sketch-enqueue-wait`` (and releases the
    worker by one batch, so the producer moves on); the folded state is
    the one a per-feature synchronous fold of the same batches gives."""
    scfg = StreamConfig(width=1 << 9, depth=3, heavy=16, ring=128)
    trk = SketchFrequencyTracker((100, 200), scfg, tracked=(0, 1))
    count = make_cell_counter([trk.features[f].cms for f in trk.tracked], trk.col_sketch)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 100, (32, 2)) for _ in range(10)]
    deltas = [count(jnp.asarray(b, jnp.int32)) for b in batches]

    permits = threading.Semaphore(0)
    fold = trk._fold

    def stalled(item):
        permits.acquire(timeout=2)  # a submit that never blocks fails, not hangs
        fold(item)

    folder = AsyncFolder(stalled, maxsize=2)
    blocked = []
    put_nowait = folder._q.put_nowait

    def counting(item):
        try:
            put_nowait(item)
        except queue.Full:
            blocked.append(item)
            permits.release()  # let the worker take one batch
            raise

    folder._q.put_nowait = counting
    trk._folder = folder

    def feed():
        for b, d in zip(batches, deltas):
            trk.observe({"sparse": b}, delta=d)
        for _ in batches:
            permits.release()
        trk.flush()

    lines = _capture(tmp_path, feed)
    waits = _named(lines, "sketch-enqueue-wait")
    assert len(batches) - 3 <= len(blocked) == len(waits)
    assert all(s.stats["depth"] in (1, 2) for s in waits)

    ref = SketchFrequencyTracker((100, 200), scfg, tracked=(0, 1))
    for b, d in zip(batches, deltas):
        d = np.asarray(d)
        for j, f in enumerate(ref.tracked):
            ref.features[f].cms.add_cells(d[j])
            ref.features[f]._ingest(b[:, f], into_sketch=False)
    for a, r in zip(trk.state_tree()[1:], ref.state_tree()[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
