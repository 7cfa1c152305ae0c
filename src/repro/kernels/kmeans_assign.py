"""Nearest-centroid assignment as a Pallas TPU kernel.

The inner loop of the paper's clustering step (Algorithm 3 line 13).
``argmin_j ||x - c_j||^2`` expands to ``argmin_j (||c_j||^2 - 2 <x, c_j>)``
(the ``||x||^2`` term is constant in j), i.e. a blocked C @ X.T on the MXU
fused with a running (min, argmin) accumulator — only the (n,) assignment
vector ever leaves the kernel, the (n, k) distance matrix is never
materialized in HBM.

Layout: the points enter transposed, ``(d, n)``, so the distance tile is
``(k_blk, n_blk)`` with the points on the 128 lanes; the (min, argmin)
reduction runs down the sublanes and lands directly in lane-dense
``(1, n)`` outputs — no relayout of a column vector into a row (d is
only 4 on the Criteo config, so a points-on-sublanes layout would pad
every tile 32x).  Every block's last two dims are a multiple of (8, 128)
or the whole array dim.

Grid: (n/n_blk, k/k_blk), k innermost; running best distance + index are
carried in the two output refs (revisited across the k axis).

VMEM per step (defaults n_blk=256, k_blk=512, f32, small dims padded to
(8, 128)): x tile 8 KiB, centroid tile + norms 2*512*128*4 = 512 KiB,
distance/iota/select temporaries 3*512*256*4 = 1.5 MiB, outputs 2 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import pallas as pl

DEFAULT_N_BLK = 256
DEFAULT_K_BLK = 512


def _kernel(xt_ref, c_ref, cn_ref, best_ref, arg_ref, *, k_blk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    xt = xt_ref[...].astype(jnp.float32)  # (d, n_blk)
    c = c_ref[...].astype(jnp.float32)  # (k_blk, d)
    # partial squared distance (missing ||x||^2, constant in j); HIGHEST
    # keeps near-ties ordered as an f32 reference orders them
    d2 = cn_ref[...] - 2.0 * jnp.dot(
        c, xt, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (k_blk, n_blk)
    local_best = jnp.min(d2, axis=0, keepdims=True)  # (1, n_blk)
    iota = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    # first index attaining the minimum == argmin's tie rule
    local_arg = jnp.min(
        jnp.where(d2 == local_best, iota, k_blk), axis=0, keepdims=True
    ) + j * k_blk
    prev_best = best_ref[...]
    take_new = local_best < prev_best
    best_ref[...] = jnp.where(take_new, local_best, prev_best)
    arg_ref[...] = jnp.where(take_new, local_arg, arg_ref[...])


def kmeans_assign_pallas(
    x: jax.Array,
    centroids: jax.Array,
    *,
    n_blk: int = DEFAULT_N_BLK,
    k_blk: int = DEFAULT_K_BLK,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """x (n, d), centroids (k, d) -> (assignments (n,) int32, partial-d2 (n,)).

    n % n_blk == 0 and k % k_blk == 0 required (`ops.kmeans_assign` pads).
    """
    n, d = x.shape
    k, _ = centroids.shape
    assert n % n_blk == 0 and k % k_blk == 0, (n, n_blk, k, k_blk)
    cn = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    best, arg = pl.pallas_call(
        functools.partial(_kernel, k_blk=k_blk),
        grid=(n // n_blk, k // k_blk),
        in_specs=[
            pl.BlockSpec((d, n_blk), lambda i, j: (0, i)),
            pl.BlockSpec((k_blk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((k_blk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda i, j: (0, i)),
            pl.BlockSpec((1, n_blk), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(x.T, centroids, cn)
    return arg[0], best[0]
