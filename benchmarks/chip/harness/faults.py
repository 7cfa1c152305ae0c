"""Faults planted underneath a run's timed path, to show that ``correct``
comes out false for each: the benchmark's tests run them on the CPU and
``calibrate.py --faults`` reads them on the chip at a cell's own size.

A step fault wraps the train step (``step_hook``), a tracker fault patches
the sketch tracker once it is built (``tracker_hook``), and a serve fault
patches the engine (``engine_hook``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def state_unchanged(step):
    """The step computes, but hands back the state it was given."""
    def broken(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return broken


def half_batch(step):
    """Half of the batch left out; the step's mean is over the rest."""
    def broken(state, batch):
        return step(state, {k: v[:, : v.shape[1] // 2] for k, v in batch.items()})
    return broken


def _delta_fault(step, change):
    def broken(state, batch):
        state, metrics = step(state, batch)
        metrics = dict(metrics)
        metrics["sketch_delta"] = change(metrics["sketch_delta"])
        return state, metrics
    return broken


def cells_to_zero(step):
    """The in-step sketch counter sends every id to the first cell."""
    def change(d):
        total = d.sum(axis=-1, keepdims=True)
        return jnp.zeros_like(d).at[..., :1].set(total)
    return _delta_fault(step, change)


def wrong_hash(step):
    """The in-step sketch counter lands each id one cell off."""
    return _delta_fault(step, lambda d: jnp.roll(d, 1, axis=-1))


def dropped_folds(trk):
    """Every other batch's fold into the tracker is dropped."""
    folder = trk._folder
    fold, n = folder._fold, [0]

    def broken(item):
        n[0] += 1
        if n[0] % 2:
            fold(item)
    folder._fold = broken


def no_head_updates(trk):
    """The SpaceSaving heads are never updated."""
    for f in trk.tracked:
        hh = trk.features[f].hh
        hh.bump = lambda slots, counts: None
        hh.offer = lambda ids, ests, sketch: None


def no_decay(trk):
    """The window's decay is skipped."""
    for f in trk.tracked:
        trk.features[f].decay = lambda gamma: None


STEP = {f.__name__: f for f in (state_unchanged, half_batch, cells_to_zero, wrong_hash)}
TRACKER = {f.__name__: f for f in (dropped_folds, no_head_updates, no_decay)}


def altered_answer(engine):
    """One answer of every micro-batch is altered where it is produced."""
    serve = engine._serve_batch

    def broken(dense, sparse):
        logits, hit = serve(dense, sparse)
        logits = np.array(logits)
        logits[0] += 0.01
        return logits, hit
    engine._serve_batch = broken


def half_answers(engine):
    """Half of each micro-batch is never answered."""
    run = engine._run

    def broken(reqs):
        return run(reqs)[: max(1, len(reqs) // 2)]
    engine._run = broken


ENGINE = {f.__name__: f for f in (altered_answer, half_answers)}


def hooks(name: str) -> dict:
    """The run hooks that plant fault ``name``."""
    for kind, table in (("step_hook", STEP), ("tracker_hook", TRACKER),
                        ("engine_hook", ENGINE)):
        if name in table:
            return {kind: table[name]}
    raise KeyError(f"no fault {name!r}")
