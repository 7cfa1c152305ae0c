"""Mesh construction and the canonical axis names.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax
init, smoke tests must keep seeing 1 device.

``make_mesh`` is the ONE place a ``jax.sharding.Mesh`` is built: every
axis is ``AxisType.Auto`` (GSPMD propagation), which is what every
jit/shard_map call site in the repo is written for — jax's own
``make_mesh`` defaults to Explicit axes, under which a jit that is not
entered into the mesh refuses mesh-sharded operands.

``DATA_AXIS`` / ``MODEL_AXIS`` are the ONE definition of the mesh axis
names: every shard_map / PartitionSpec call site routes through them (or
through ``batch_axes``/``model_axis``) instead of ad-hoc string
literals, so the audit's source rules can grep one symbol.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(shape, axes, *, devices=None):
    """An all-Auto-axes mesh of ``shape`` over ``devices`` (default: the
    first prod(shape) of ``jax.devices()``; a described topology's
    ``devices`` for chip-less compiles)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod \
        else (DATA_AXIS, MODEL_AXIS)
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, *, devices=None):
    """Small (data, model) mesh over the host's devices (or ``devices``).
    ``model`` is honoured exactly (the slab shard count must divide k);
    ``data`` shrinks to fit the device count."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if model > n:
        raise ValueError(f"model={model} exceeds device count {n}")
    data = max(1, min(data, n // model))
    return make_mesh((data, model), (DATA_AXIS, MODEL_AXIS), devices=devices)


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod axis included when present).

    The model axis is deliberately excluded: LM layers treat "model" in
    their batch axes as the FSDP signal.  DLRM's sharded step, which
    spreads the batch over ALL devices, uses ``all_batch_axes``."""
    names = mesh.axis_names
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in names)


def all_batch_axes(mesh) -> tuple[str, ...]:
    """Batch axes spanning EVERY device — the DLRM sharded-step layout:
    the batch dim is sharded over (data × model) so each device runs
    MLPs on a distinct slice while the supertable stays model-sharded."""
    axes = batch_axes(mesh)
    if model_axis(mesh) is not None:
        axes = axes + (MODEL_AXIS,)
    return axes


def model_axis(mesh) -> str | None:
    """The model-parallel axis name, or None when the mesh has no
    nontrivial model dimension (1-device / pure-data-parallel)."""
    names = mesh.axis_names
    if MODEL_AXIS in names and mesh.shape.get(MODEL_AXIS, 1) > 1:
        return MODEL_AXIS
    return None


def ptr_partition_spec(c: int, d1: int, n_shards: int, axis: str = MODEL_AXIS):
    """At-rest layout for a (c, d1) CCE pointer table over ``n_shards``.

    Prefer id-sharding (dim 1 — matches the transition kernels' compute
    layout, so ``cluster_sharded``/``remap_moments_sharded`` consume it
    reshard-free); jax rejects uneven shardings, so ragged vocabs
    (Criteo's 10_131_227 is odd) fall back to column-sharding (dim 0 —
    one reshard all-to-all at transition time), and replicate only when
    nothing divides.  The ONE definition of this policy: the trainer's
    state specs and the audit harness both route through it."""
    from jax.sharding import PartitionSpec as P

    if n_shards <= 1:
        return P()
    if d1 % n_shards == 0:
        return P(None, axis)
    if c % n_shards == 0:
        return P(axis, None)
    return P()
