"""Fused CCE multi-column embedding lookup as a Pallas TPU kernel.

TPU adaptation of the paper's hot loop (`concat_i M_i[h_i(id)] + M'_i[h'_i(id)]`,
Algorithm 3 line 8).  GPUs do this with a memory-bound sparse gather; TPUs
have no fast random gather but a 128x128 systolic MXU, so we express the
gather as a *blocked one-hot matmul*:

    M[idx]  ==  onehot(idx) @ M

The one-hot block ``(B_blk, k_blk)`` is built in-register from an
``iota == idx`` comparison (it never exists in HBM), multiplied against an
``M`` tile staged in VMEM by the BlockSpec pipeline, and accumulated over
k-blocks.  The CCE sum over the main + helper table fuses into the same
accumulation loop, so the 2c gathers of Algorithm 3 are a single kernel
launch.  The backward scatter-add is the transposed matmul
``onehot.T @ dout`` — same trick, and deterministic (no GPU-style atomics).

The kernel is TABLE-COUNT-GENERIC: T is any stacked sub-table count
(T=2 CCE, T=1 CE-concat / hashed / full tables), and a NEGATIVE row index
is a free no-op sentinel — ``local == iota`` never matches, so the lane
contributes exactly zero forward and exactly zero backward.  That is what
lets the ``EmbeddingCollection`` fuse methods with different T into ONE
supertable launch (a T=1 method pads its row tensor with -1; see
DESIGN.md §6) without masks or extra branches in the kernel.

Layout (chosen so every block's last two dims are TPU-legal — a multiple
of (8, 128) or the whole array dim — and the batch rides the 128 lanes):

    ids     (c, T, B)        block (1, T, B_blk)
    tables  (c, T, dsub, k)  block (1, T, dsub, k_blk)
    out     (c, dsub, B)     block (1, dsub, B_blk)

so the one-hot is built transposed, ``(k_blk, B_blk)``, and the forward is
``tab^T @ onehot^T`` -> ``(dsub, B_blk)``: dsub is only 4 on the Criteo
config, and putting it on the sublanes instead of the lanes keeps the
output tile dense.  The public (c, B, T) / (c, T, k, dsub) / (B, c*dsub)
layouts are converted by the ``*_pallas`` wrappers below (cheap XLA
transposes of the small slab and of the batch-sized ids/output).

Grid: (c columns, B/B_blk batch blocks, k/k_blk codebook blocks); the
k axis is innermost so the output block revisits and accumulates.  The
backward's grid is (c, k/k_blk, B/B_blk) with the batch innermost.

The dots run at ``Precision.HIGHEST``: a one-hot matmul must be an exact
gather, and a reduced-precision MXU pass would round f32 table values
to bf16 (a 0/1 operand times an f32 value split into bf16 parts sums
back to that value exactly).

VMEM working set per step (defaults B_blk=256, k_blk=512, dsub=4, f32;
sub-(8, 128) dims pad up): ids 8 KiB, tables 2*8*512*4 = 32 KiB,
one-hot/iota 2*512*256*4 = 1 MiB, out 8 KiB — far under the 16 MiB
scoped limit, double buffering included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import pallas as pl


DEFAULT_B_BLK = 256
DEFAULT_K_BLK = 512


_HIGHEST = jax.lax.Precision.HIGHEST


def _fwd_kernel(idx_ref, tab_ref, out_ref, *, k_blk: int, n_tables: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    local = idx_ref[0] - j * k_blk  # (T, B_blk) row ids relative to this k block
    iota = jax.lax.broadcasted_iota(jnp.int32, (k_blk, local.shape[1]), 0)
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)  # (dsub, B_blk)
    for t in range(n_tables):
        onehot_t = (iota == local[t : t + 1, :]).astype(tab_ref.dtype)
        acc += jnp.dot(tab_ref[0, t], onehot_t, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
    out_ref[0] += acc.astype(out_ref.dtype)


def _bwd_kernel(idx_ref, dout_ref, dtab_ref, *, k_blk: int, n_tables: int):
    """dM[i, t]^T = dout[i] @ onehot(idx[i, t]) — grid (c, nk, nb)."""
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        dtab_ref[...] = jnp.zeros_like(dtab_ref)

    j = pl.program_id(1)
    local = idx_ref[0] - j * k_blk  # (T, B_blk)
    iota = jax.lax.broadcasted_iota(jnp.int32, (k_blk, local.shape[1]), 0)
    dout = dout_ref[0]  # (dsub, B_blk)
    for t in range(n_tables):
        onehot_t = (iota == local[t : t + 1, :]).astype(dout.dtype)
        dtab_ref[0, t] += jax.lax.dot_general(
            dout, onehot_t, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(dtab_ref.dtype)  # (dsub, k_blk)


def cce_lookup_fwd_pallas(
    idx: jax.Array,
    tables: jax.Array,
    *,
    b_blk: int = DEFAULT_B_BLK,
    k_blk: int = DEFAULT_K_BLK,
    interpret: bool = False,
) -> jax.Array:
    """Forward lookup.  idx (c, B, T) int32; tables (c, T, k, dsub).

    Returns (B, c, dsub).  B % b_blk == 0 and k % k_blk == 0 are required —
    `ops.cce_lookup` pads.
    """
    c, B, T = idx.shape
    _, _, k, dsub = tables.shape
    assert B % b_blk == 0 and k % k_blk == 0, (B, b_blk, k, k_blk)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, k_blk=k_blk, n_tables=T),
        grid=(c, B // b_blk, k // k_blk),
        in_specs=[
            pl.BlockSpec((1, T, b_blk), lambda i, b, j: (i, 0, b)),
            pl.BlockSpec((1, T, dsub, k_blk), lambda i, b, j: (i, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, dsub, b_blk), lambda i, b, j: (i, 0, b)),
        out_shape=jax.ShapeDtypeStruct((c, dsub, B), tables.dtype),
        interpret=interpret,
    )(jnp.swapaxes(idx, 1, 2), jnp.swapaxes(tables, 2, 3))
    return jnp.transpose(out, (2, 0, 1))


def cce_lookup_bwd_pallas(
    idx: jax.Array,
    dout: jax.Array,
    k: int,
    *,
    b_blk: int = DEFAULT_B_BLK,
    k_blk: int = DEFAULT_K_BLK,
    interpret: bool = False,
) -> jax.Array:
    """Backward scatter-add.  idx (c, B, T); dout (B, c, dsub) -> dtables
    (c, T, k, dsub)."""
    c, B, T = idx.shape
    dsub = dout.shape[-1]
    assert B % b_blk == 0 and k % k_blk == 0
    dtab = pl.pallas_call(
        functools.partial(_bwd_kernel, k_blk=k_blk, n_tables=T),
        grid=(c, k // k_blk, B // b_blk),
        in_specs=[
            pl.BlockSpec((1, T, b_blk), lambda i, j, b: (i, 0, b)),
            pl.BlockSpec((1, dsub, b_blk), lambda i, j, b: (i, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, T, dsub, k_blk), lambda i, j, b: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, T, dsub, k), dout.dtype),
        interpret=interpret,
    )(jnp.swapaxes(idx, 1, 2), jnp.transpose(dout, (1, 2, 0)))
    return jnp.swapaxes(dtab, 2, 3)
