"""The clustering transition, shared by every model (DESIGN.md §2).

``transition_table`` is one CCE table's complete transition:  derive a
sampling seed from the transition key, build the k-means point set from
observed id frequencies when a histogram exists (count-WEIGHTED — every
observed id once, weighted by frequency), cluster, and build the
moment-update function that ``remap_opt_state`` applies to each optimizer
slot (computing the per-cluster counts once so Adam's m AND v reuse them).

``transition_collection`` runs it across an ``EmbeddingCollection``:
per-feature slices come out of the grouped supertables, transition
independently (each with its own key/histogram), and re-stack — so the
training loop keeps carrying ONE stacked slab per group through the jitted
step while the transition stays a per-table algorithm.  The LM launcher
uses ``transition_table`` directly (one vocab table); centralizing both
here keeps the paths from drifting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.remap import collection_moment_updater, zeros_like_moments
from repro.stream.points import points_from_counts


def _draw_points(counts, n: int, seed: int):
    """(ids, weights) from a per-feature count source: a DENSE histogram
    array, or a sketch-backed provider (``repro.stream.FeatureSketch``)
    exposing ``points(n, seed)`` — exact head + unbiased tail at
    vocab-independent tracker memory."""
    if hasattr(counts, "points"):
        return counts.points(n, seed)
    return points_from_counts(counts, n, seed)


def _dense_weights(counts, d1: int) -> np.ndarray:
    """Per-id weights for the count-weighted moment remap.  Dense
    histograms are used verbatim; a sketch provider streams an O(d1)
    TRANSIENT estimate (same order as the transition's assign_all pass —
    tracker state stays O(sketch))."""
    if hasattr(counts, "id_weights"):
        return counts.id_weights(d1)
    return np.asarray(counts)


@functools.lru_cache(maxsize=None)
def _cluster_program(table, mesh, axis_name, chunk_size, use_kernel,
                     max_points_per_centroid):
    """``table.cluster_sharded`` as one jitted SPMD program (the one the
    audit captures), built once per table and mesh so every later
    transition reuses its trace and compile.  Run eagerly, shard_map
    compiles each primitive of the k-means loop and chunked assignment
    as a program of its own."""
    return jax.jit(functools.partial(
        table.cluster_sharded, mesh=mesh, axis_name=axis_name,
        chunk_size=chunk_size, use_kernel=use_kernel,
        max_points_per_centroid=max_points_per_centroid,
    ))


def transition_table(
    table,
    key,
    params,
    buffers,
    *,
    counts=None,
    policy: str = "remap",
    chunk_size: int | None = None,
    use_kernel: bool | None = None,
    max_points_per_centroid: int = 256,
    mesh=None,
    shard_axis: str | None = None,
):
    """Returns ``(new_params, new_buffers, update_moments)`` for one CCE
    table.  ``counts`` is the table's observed id histogram — a dense
    array OR a sketch provider with ``points``/``id_weights`` (see
    ``repro.stream``); when present the k-means runs count-WEIGHTED on
    the observed ids (the paper's epoch-boundary distribution, exactly —
    not a with-replacement approximation of it) and the moment remap
    averages with the same weights.  None or all-zero falls back to
    uniform subsampling.  ``update_moments(moment_subtree)`` remaps/
    resets/keeps that table's per-row optimizer moments per ``policy``.

    ``mesh``/``shard_axis`` route every O(d1) phase through the sharded
    implementations (``cluster_sharded`` / ``remap_moments_sharded``) —
    id ranges and pointer tables shard over ``shard_axis``, so the
    transition never assembles a full (c, d1) ptr on one device.  On a
    1-device axis the sharded paths are bit-identical to the serial
    ones (same key schedule), so the clustering trajectory does not
    depend on the mesh."""
    sample_ids = sample_weights = id_weights = None
    if counts is not None:
        seed = int(
            jax.random.randint(jax.random.fold_in(key, 10_007), (), 0, 2**31 - 1)
        )
        drawn = _draw_points(
            counts, min(table.d1, max_points_per_centroid * table.k), seed
        )
        if drawn is not None:
            sample_ids = jnp.asarray(drawn[0])
            sample_weights = jnp.asarray(drawn[1], jnp.float32)
            id_weights = jnp.asarray(_dense_weights(counts, table.d1), jnp.float32)
    sharded = mesh is not None and shard_axis is not None
    if sharded:
        cluster = _cluster_program(
            table, mesh, shard_axis, chunk_size, use_kernel,
            max_points_per_centroid,
        )
        new_params, new_buffers = cluster(
            key, params, buffers,
            sample_ids=sample_ids, sample_weights=sample_weights,
        )
    else:
        new_params, new_buffers = table.cluster(
            key, params, buffers,
            sample_ids=sample_ids, sample_weights=sample_weights,
            chunk_size=chunk_size, use_kernel=use_kernel,
            max_points_per_centroid=max_points_per_centroid,
        )
    cluster_counts = (
        table.assignment_counts(new_buffers)
        if policy == "remap" and not sharded else None
    )

    def update_moments(moments):
        if policy == "keep":
            return moments
        if policy == "reset":
            return zeros_like_moments(moments)
        if sharded:
            # counts accumulate inside the sharded pass (masked ones) —
            # no full-ptr bincount on one device
            return table.remap_moments_sharded(
                moments, buffers, new_buffers, mesh, axis_name=shard_axis,
                chunk_size=chunk_size, id_weights=id_weights,
            )
        return table.remap_moments(
            moments, buffers, new_buffers,
            chunk_size=chunk_size, counts=cluster_counts, id_weights=id_weights,
        )

    return new_params, new_buffers, update_moments


def transition_collection(
    coll,
    key,
    emb_params,
    emb_buffers,
    *,
    id_counts=None,
    policy: str = "remap",
    chunk_size: int | None = None,
    use_kernel: bool | None = None,
    max_points_per_centroid: int = 256,
    mesh=None,
    shard_axis: str | None = None,
):
    """Transition every CCE table behind an ``EmbeddingCollection``.

    ``emb_params``/``emb_buffers`` are the GROUPED layout; each CCE
    feature's (c, 2, k, dsub) block is sliced out of its (possibly
    method-mixed universal) group, transitioned with
    ``jax.random.fold_in(key, feature_index)`` (the same key schedule as
    the legacy per-table loop, so transitions replay identically from a
    checkpoint), and re-stacked; a group's non-CCE members (full/hash/ce
    tables sharing the supertable launch) pass through untouched.
    Returns ``(new_params, new_buffers, update_emb)`` where ``update_emb``
    transforms a grouped moments["emb"] list group-wise (see
    ``optim.remap.collection_moment_updater``).  ``id_counts`` indexes
    per-feature histograms by GLOBAL feature index.
    """
    from repro.core.cce import CCE

    new_p, new_b = list(emb_params), list(emb_buffers)
    group_updates: dict[int, dict[int, object]] = {}
    for g, grp in enumerate(coll.groups):
        cce_locals = [
            f_local for f_local, t in enumerate(grp.tables) if isinstance(t, CCE)
        ]
        if not cce_locals:
            continue
        per_p = coll.unstack_group_params(grp, emb_params[g])
        per_b = list(emb_buffers[g])
        fns = {}
        for f_local in cce_locals:
            i = grp.features[f_local]
            per_p[f_local], per_b[f_local], fns[f_local] = transition_table(
                grp.tables[f_local], jax.random.fold_in(key, i),
                per_p[f_local], per_b[f_local],
                counts=id_counts[i] if id_counts is not None else None,
                policy=policy, chunk_size=chunk_size, use_kernel=use_kernel,
                max_points_per_centroid=max_points_per_centroid,
                mesh=mesh, shard_axis=shard_axis,
            )
        new_p[g] = coll.stack_group_params(grp, per_p)
        new_b[g] = per_b
        group_updates[g] = fns
    return new_p, new_b, collection_moment_updater(coll, group_updates)
