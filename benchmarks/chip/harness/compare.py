"""The numbers that decide ``correct``, each against its limit.

Training (the first three steps of the object the window then drives):

* ``loss_gap``: the widest relative gap between a step's loss and the
  reference's, over the three steps;
* ``grad_gap_median``: the first gradient as the optimizer applied it,
  worked out from the state after one step, ``(p0 - p1) / lr``: per leaf
  the gap between the program's norm and the reference's over the larger
  of the reference's norm of that leaf and of the median leaf, the median
  over the leaves.  The worst leaf (``grad_gap``) is read, not compared:
  at lr 1e-3 a float32 state holds an update of a small gradient element
  to a few ulps, so the worst leaf's norm swings by whole percents with
  the rounding of a few elements (see PERF.md);
* ``change_gap_median`` (and ``change_gap``): the same for the
  parameters' change after three steps.  Leaves whose reference gradient
  is under a thousandth of the median leaf's move by round-off alone and
  are left out of all four;
* ``sketch_delta_gap``: the in-step count-min delta of each of the three
  steps against the reference's cells of the same batch, the widest
  absolute gap of a cell (exact, limit 0);
* ``head_miss_share`` and ``head_count_gap``: the tracker's SpaceSaving
  heads against the exact, window-decayed count of the ids it was fed,
  after the three steps and again after the window: of the ids that
  outweigh every id below the ``head_top`` heaviest, the share missing
  from a feature's head, and the widest relative gap between a resident
  id's head count and its exact count; the worst feature.

Serving: ``logit_gap``, the widest absolute gap between a served logit and
the reference's, over a sample of the window's queries drawn from the
seed, the longest among them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEGLIGIBLE = 1e-3


def diff_norms(a, b):
    """Per-leaf ||a - b|| as one vector (jit it with whatever gets ``b``)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y)))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def _leaf_gaps(prog, ref, counted) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    return (np.abs(prog - ref) / scale)[counted]


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [3], "grad": per-leaf norms of the
    first applied gradient, "change": per-leaf norms of p3 - p0}."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    g_ref = np.asarray(ref["grad"], np.float64)
    counted = g_ref >= NEGLIGIBLE * np.median(g_ref)
    grad = _leaf_gaps(prog["grad"], g_ref, counted)
    change = _leaf_gaps(prog["change"], ref["change"], counted)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(grad.max()),
        "change_gap": float(change.max()),
        "grad_gap_median": float(np.median(grad)),
        "change_gap_median": float(np.median(change)),
    }


def sketch_delta_gap(deltas, want) -> float:
    """Widest |program cell - reference cell| over the steps; a missing
    delta fails."""
    if len(deltas) != len(want):
        return float("inf")
    gaps = [np.abs(np.asarray(d, np.int64) - w).max() for d, w in zip(deltas, want)]
    return float(max(gaps)) if gaps else float("inf")


def head_numbers(heads: dict, exact: dict, top: int) -> dict:
    """``heads``/``exact``: {feature: (ids, counts)}, exact heaviest first.
    The ids checked are those heavier than the ``top + 1``-th heaviest, so
    a tie at the edge decides nothing."""
    miss = gap = 0.0
    for f, (eids, ecnt) in exact.items():
        if len(ecnt) <= top:
            want = len(ecnt)
        else:
            want = int(np.searchsorted(-ecnt, -ecnt[top], side="left"))
        if want == 0:
            continue
        hids, hcnt = (np.asarray(x) for x in heads.get(f, ([], [])))
        order = np.argsort(hids, kind="stable")
        hids, hcnt = hids[order], hcnt[order]
        pos = np.clip(np.searchsorted(hids, eids[:want]), 0, max(len(hids) - 1, 0))
        found = (hids[pos] == eids[:want]) if len(hids) else np.zeros(want, bool)
        miss = max(miss, 1.0 - found.mean())
        if found.any():
            rel = np.abs(hcnt[pos[found]] - ecnt[:want][found]) / ecnt[:want][found]
            gap = max(gap, float(rel.max()))
    return {"head_miss_share": float(miss), "head_count_gap": gap}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}}).  A number
    that is missing or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
