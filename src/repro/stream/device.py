"""Device-side batch sketch updates + asynchronous host fold.

The host conservative-update path costs O(batch · depth) numpy work per
step IN the training loop; at pod batch sizes that serializes against
the jitted step.  This module removes tracking from the critical path:

  * ``cell_count_fn`` builds ONE pure function for all tracked features:
    hash every id of the (B, F_tracked) sparse block with each feature's
    multiply-shift coefficients (the SAME coefficients the host sketch
    uses, so device cells == host cells) and segment-sum the hits into an
    (F_tracked, depth, width) increment tensor.  ``make_step_cell_counter``
    EMBEDS it into the jitted train step (``make_train_step(sketch_fn=)``)
    so the delta rides the step's single launch — tracking adds zero
    extra device dispatches; ``make_cell_counter`` is the standalone
    jitted dispatcher (one extra async dispatch per batch) for trackers
    running outside a train step.
  * ``AsyncFolder`` drains (device_delta, host_ids) pairs on a single
    background thread: the ``device_get`` of the delta and the
    O(unique-ids) head/ring bookkeeping block the FOLD thread, never the
    step.  ``flush()`` is the barrier the tracker takes before sampling,
    statistics, or checkpointing — fold order is FIFO, so flushed state
    is a pure function of the observed batch sequence and restart-exact
    resume holds with the async path enabled.
"""
from __future__ import annotations

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span


def cell_count_fn(sketches, col_sketch):
    """PURE (B, n_cols) int32 -> (F, depth, width) int32 cell-increment counter
    over ``sketches`` (the tracked features' ``CountMinSketch`` objects,
    which must share width/depth — one ``StreamConfig`` builds them, so
    they do).  Not jitted: the caller either wraps it (the standalone
    dispatcher below) or INLINES it into an already-jitted program — the
    train step embeds it via ``make_step_cell_counter`` so sketch tracking
    adds ZERO extra device dispatches (DESIGN.md §6).

    ``col_sketch`` gives each input column's sketch (its index in
    ``sketches``): a column's ids are hashed with, and counted into, its
    feature's sketch, so every id of a multi-hot bag counts (one column a
    feature without bags: ``arange(F)``)."""
    widths = {s.width for s in sketches}
    depths = {s.depth for s in sketches}
    if len(widths) != 1 or len(depths) != 1:
        raise ValueError("tracked sketches must share width/depth")
    (width,), (depth,) = widths, depths
    n_feat = len(sketches)
    col_sketch = np.asarray(col_sketch)
    a = jnp.asarray(np.stack([s.a for s in sketches])[col_sketch])  # (n_cols, depth) uint32
    b = jnp.asarray(np.stack([s.b for s in sketches])[col_sketch])
    # each column's (feature, row) plane: where its cells start in the delta
    base = ((col_sketch[:, None] * depth + np.arange(depth)) * width).astype(np.uint32)
    shift = int(sketches[0].shift)

    def count_cells(sparse):  # (B, n_cols) int32
        x = sparse.T.astype(jnp.uint32)  # (n_cols, B)
        cells = (a[:, :, None] * x[:, None, :] + b[:, :, None]) >> shift
        # one flat scatter-add across every (feature, row) plane
        flat = (cells + base[:, :, None]).reshape(-1)
        delta = jnp.zeros(n_feat * depth * width, jnp.int32).at[
            flat.astype(jnp.int32)
        ].add(1)
        return delta.reshape(n_feat, depth, width)

    return count_cells


def make_cell_counter(sketches, col_sketch):
    """Standalone jitted dispatcher around ``cell_count_fn`` — the
    tracker's own fallback path when the train step does not embed the
    counter (one extra dispatch per batch)."""
    return jax.jit(cell_count_fn(sketches, col_sketch))


def make_step_cell_counter(tracker):
    """The ``sketch_fn`` a ``SketchFrequencyTracker`` contributes to
    ``train.loop.make_train_step``: microbatch dict -> (F_tracked, depth,
    width) int32 cell delta, computed INSIDE the jitted step (selecting
    the tracked sparse columns with the same hash coefficients the host
    sketch uses, so in-step cells == host cells bit for bit; every id of a
    tracked feature's bag counts).  Returns None when the tracker has no
    sketch-backed features (dense tracker, or nothing tracked) — the step
    then carries no delta."""
    tracked = getattr(tracker, "tracked", None)
    if not tracked:
        return None
    fn = cell_count_fn([tracker.features[f].cms for f in tracked], tracker.col_sketch)
    cols = tracker.tracked_cols
    key = tracker.key

    def count(microbatch):
        sparse = jnp.take(microbatch[key], jnp.asarray(cols), axis=1)
        return fn(sparse.astype(jnp.int32))

    return count


class AsyncFolder:
    """FIFO background folder with error propagation on the barrier."""

    def __init__(self, fold_fn, maxsize: int = 64):
        self._fold = fold_fn
        self._q: queue.Queue = queue.Queue(maxsize=maxsize)
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        while True:
            item = self._q.get()
            try:
                if self._error is None:  # after an error, drain without work
                    self._fold(item)
            except BaseException as e:  # surfaced on the next flush()
                self._error = e
            finally:
                self._q.task_done()

    def submit(self, item) -> None:
        """Enqueue ``item``; blocks while the queue is full (backpressure
        instead of unbounded lag), inside a ``sketch-enqueue-wait`` span.
        One producer, so trying first keeps the FIFO order."""
        if self._error is not None:
            self.flush()  # raises
        try:
            self._q.put_nowait(item)
        except queue.Full:
            with span("sketch-enqueue-wait", depth=self._q.qsize()):
                self._q.put(item)

    def flush(self) -> None:
        self._q.join()
        if self._error is not None:
            err, self._error = self._error, None
            raise err
