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

Sum-pooled bags (multi-hot features): a column whose feature looks up a
bag of ``bag`` ids per example carries ``S = bag * T`` row slots (slot
``l * T + t``: the bag's l-th id in sub-table t), and its block is
multi-hot: ``sum_l (iota == local_l)`` is built before the same MXU
product, whose backward is the same transposed product.  So the MXU work
of a column does not grow with its bag; the compares and the index bytes
grow with the column's OWN bag, never the longest: a launch covers one
RUN of adjacent columns of equal bag length (``col0`` offsets its table
and gradient blocks into the shared slab), and ``ops.cce_lookup`` issues
one launch per run.  Padding every column to the longest bag would do
~12x the compares on MLPerf's Criteo-1TB bags (4 x 214 ids per example
against 104 columns x 100).  Without bags, S = T and the kernel is the
one-hot kernel.

Layout (chosen so every block's last two dims are TPU-legal — a multiple
of (8, 128) or the whole array dim — and the batch rides the 128 lanes):

    ids     (c, S, B)        block (1, S, B_blk)
    tables  (C, T, dsub, k)  block (1, T, dsub, k_blk)
    out     (c, dsub, B)     block (1, dsub, B_blk)

so the one-hot is built transposed, ``(k_blk, B_blk)``, and the forward is
``tab^T @ onehot^T`` -> ``(dsub, B_blk)``: dsub is 4 on the Criteo Kaggle
config and 32 on Criteo-1TB, and putting it on the sublanes instead of
the lanes keeps the output tile dense.  ``ops.cce_lookup`` converts the
public (c, B, S) / (c, T, k, dsub) / (B, c*dsub) layouts (cheap XLA
transposes of the small slab and of the batch-sized ids/output, once for
all runs).

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
scoped limit, double buffering included.  A bag of 100 (S = 200) adds
200 KiB of ids and one (k_blk, B_blk) int32 count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import pallas as pl


DEFAULT_B_BLK = 256
DEFAULT_K_BLK = 512


_HIGHEST = jax.lax.Precision.HIGHEST


def _hot(idx_ref, iota, offset, t: int, n_tables: int, bag: int, dtype):
    """Sub-table ``t``'s one-hot block ``(k_blk, B_blk)``, or for a bag its
    multi-hot block: ``sum_l (iota == local_l)`` over the slots ``l * T +
    t``, counted in int32 and cast once (a count is a small integer, so
    the cast is exact)."""
    if bag == 1:
        return (iota == idx_ref[0, t : t + 1, :] - offset).astype(dtype)
    cnt = None
    for s in range(t, bag * n_tables, n_tables):
        hit = (iota == idx_ref[0, s : s + 1, :] - offset).astype(jnp.int32)
        cnt = hit if cnt is None else cnt + hit
    return cnt.astype(dtype)


def _fwd_kernel(idx_ref, tab_ref, out_ref, *, k_blk: int, n_tables: int, bag: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota = jax.lax.broadcasted_iota(jnp.int32, (k_blk, idx_ref.shape[2]), 0)
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)  # (dsub, B_blk)
    for t in range(n_tables):
        hot_t = _hot(idx_ref, iota, j * k_blk, t, n_tables, bag, tab_ref.dtype)
        acc += jnp.dot(tab_ref[0, t], hot_t, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
    out_ref[0] += acc.astype(out_ref.dtype)


def _bwd_kernel(idx_ref, dout_ref, dtab_ref, *, k_blk: int, n_tables: int, bag: int):
    """dM[i, t]^T = dout[i] @ hot(idx[i, t]) — grid (c, nk, nb)."""
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        dtab_ref[...] = jnp.zeros_like(dtab_ref)

    j = pl.program_id(1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (k_blk, idx_ref.shape[2]), 0)
    dout = dout_ref[0]  # (dsub, B_blk)
    for t in range(n_tables):
        hot_t = _hot(idx_ref, iota, j * k_blk, t, n_tables, bag, dout.dtype)
        dtab_ref[0, t] += jax.lax.dot_general(
            dout, hot_t, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(dtab_ref.dtype)  # (dsub, k_blk)


def cce_lookup_fwd_pallas(
    idx: jax.Array,
    tables: jax.Array,
    *,
    col0: int = 0,
    b_blk: int = DEFAULT_B_BLK,
    k_blk: int = DEFAULT_K_BLK,
    interpret: bool = False,
) -> jax.Array:
    """Forward lookup of one run of columns, in the kernel's layout.

    idx (c, S, B) int32, S = bag * T slots; tables (C, T, dsub, k), of
    which the run reads columns ``col0 .. col0 + c``.  Returns (c, dsub,
    B).  B % b_blk == 0 and k % k_blk == 0 are required — `ops.cce_lookup`
    pads.
    """
    c, S, B = idx.shape
    _, T, dsub, k = tables.shape
    assert B % b_blk == 0 and k % k_blk == 0 and S % T == 0, (B, b_blk, k, k_blk, S, T)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k_blk=k_blk, n_tables=T, bag=S // T),
        grid=(c, B // b_blk, k // k_blk),
        in_specs=[
            pl.BlockSpec((1, S, b_blk), lambda i, b, j: (i, 0, b)),
            pl.BlockSpec((1, T, dsub, k_blk), lambda i, b, j: (col0 + i, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, dsub, b_blk), lambda i, b, j: (i, 0, b)),
        out_shape=jax.ShapeDtypeStruct((c, dsub, B), tables.dtype),
        interpret=interpret,
    )(idx, tables)


def cce_lookup_bwd_pallas(
    idx: jax.Array,
    dout: jax.Array,
    k: int,
    n_tables: int,
    *,
    col0: int = 0,
    b_blk: int = DEFAULT_B_BLK,
    k_blk: int = DEFAULT_K_BLK,
    interpret: bool = False,
) -> jax.Array:
    """Backward scatter-add of one run, in the kernel's layout.  idx
    (c, S, B), S = bag * n_tables; dout (C, dsub, B), of which the run
    reads columns ``col0 .. col0 + c`` -> dtables (c, n_tables, dsub, k)."""
    c, S, B = idx.shape
    dsub = dout.shape[1]
    T = n_tables
    assert B % b_blk == 0 and k % k_blk == 0 and S % T == 0
    return pl.pallas_call(
        functools.partial(_bwd_kernel, k_blk=k_blk, n_tables=T, bag=S // T),
        grid=(c, k // k_blk, B // b_blk),
        in_specs=[
            pl.BlockSpec((1, S, b_blk), lambda i, j, b: (i, 0, b)),
            pl.BlockSpec((1, dsub, b_blk), lambda i, j, b: (col0 + i, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, T, dsub, k_blk), lambda i, j, b: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, T, dsub, k), dout.dtype),
        interpret=interpret,
    )(idx, dout)
