"""Optimizer-state handling for the CCE clustering transition.

``CCE.cluster`` rewrites a table's rows (centroids into the main table,
zeros into the helper) and its pointer array, but the momentum / Adam
moments of those rows still describe the OLD rows.  Applying them
unchanged is the dynamic-reassignment failure mode CAFE (Zhang et al.,
2023) warns about — stale second moments throttle the effective step size
of freshly-merged rows arbitrarily — and the reason Shi et al. (2020)
keep compositional tables optimizer-stable.  ``remap_opt_state`` threads
a moment transform through the optimizer-state tree, policy-selected:

  * ``"remap"`` — per-row moments follow the cluster assignments (mean of
    the merged rows' moments, zeros for the fresh helper table — see
    ``CCE.remap_moments``, or ``CCE.remap_moments_sharded`` when the
    transition runs over a mesh: the O(d1) averaging pass then shards its
    id ranges and pointer operands over the mesh axis and psums the
    per-cluster sums, bit-identical on a 1-device axis), the moment-space
    analog of setting the main table to the centroids.
  * ``"reset"`` — zero the transitioned tables' moments (fresh start).
  * ``"keep"`` — leave the state untouched (the pre-fix behavior, kept
    for ablation).

Only per-row moment slots are touched; scalar slots (the Adam step count
``t``) pass through so bias correction stays continuous across the
transition and checkpoint resume stays restart-exact.

Under a model-sharded trainer (launch.steps.dlrm_state_specs) the moment
slabs enter sharded exactly like their params; the eager transition's
outputs land wherever jax puts them and the Trainer device_puts the whole
state back onto the step's layout (``Trainer._place``) before the next
donated step — this module stays layout-agnostic.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

Pytree = Any

#: Per-row moment slots of the optimizers in this repo
#: (sgd-momentum: {"m"}; adamw: {"m", "v"}; adagrad: {"a"}).
MOMENT_KEYS = ("m", "v", "a")

POLICIES = ("remap", "reset", "keep")


def remap_opt_state(
    opt: Pytree,
    update_fn: Callable[[Pytree, str], Pytree],
    *,
    policy: str = "remap",
    moment_keys: tuple[str, ...] = MOMENT_KEYS,
) -> Pytree:
    """Apply ``update_fn(moment_tree, slot_name)`` to each per-parameter
    moment tree in an optimizer state.  ``update_fn`` receives the full
    moment tree (same structure as params) and replaces only the subtrees
    belonging to transitioned tables — non-embedding moments flow through
    untouched.  Plain-SGD state ({}) and ``policy="keep"`` are no-ops."""
    if policy not in POLICIES:
        raise ValueError(f"unknown transition policy {policy!r}; want one of {POLICIES}")
    if opt is None or policy == "keep" or not opt:
        return opt
    new = dict(opt)
    for slot in moment_keys:
        if slot in new:
            new[slot] = update_fn(new[slot], slot)
    return new


def zeros_like_moments(moments: Pytree) -> Pytree:
    """The ``"reset"`` policy for one table's moment subtree."""
    return jax.tree.map(jnp.zeros_like, moments)


def collection_moment_updater(coll, group_updates):
    """Moment transform for the GROUPED embedding layout.

    Optimizer moments mirror params, so under an ``EmbeddingCollection``
    a CCE group's moments live in one stacked (F·c, 2, k, dsub) slab.
    ``group_updates`` maps group index -> {feature-local index ->
    per-feature moment-update fn (from ``transition_table``)}; the
    returned function slices each transitioned feature's block out of the
    slab, applies its update, and re-stacks — zero-padded moment rows
    (ragged codebooks) stay zero, mirroring their never-touched params.
    Applied once per moment slot (Adam's m AND v) by ``remap_opt_state``.
    """

    def update(emb_moments):
        out = list(emb_moments)
        for g, fns in group_updates.items():
            grp = coll.groups[g]
            per = coll.unstack_group_params(grp, emb_moments[g])
            for f_local, fn in fns.items():
                per[f_local] = fn(per[f_local])
            out[g] = coll.stack_group_params(grp, per)
        return out

    return update
