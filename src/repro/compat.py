"""The one home of ``jax.experimental`` and of the sharding call shapes.

The repo targets the installed jax (0.9): ``jax.shard_map``,
``jax.sharding.set_mesh`` and dict-valued ``Compiled.cost_analysis()``.
Call sites import from here so the ``no-raw-experimental`` source rule
can keep every experimental import in this file.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with varying-manual-axes checking off: bodies that
    call pallas kernels (custom_vjp around ``pallas_call``) carry no
    replication rule, so the checker would refuse them."""
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# pallas has no stable top-level home yet; this is the ONE sanctioned
# import of it (kernels do `from repro.compat import pallas as pl`, and
# the no-raw-experimental source rule keeps it that way)
from jax.experimental import pallas  # noqa: E402,F401

#: makes ``mesh`` ambient for jit/PartitionSpec (a context manager)
set_mesh = jax.sharding.set_mesh


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (None -> {})."""
    return dict(compiled.cost_analysis() or {})
