"""Public jit'd wrappers for the Pallas kernels.

Handles: shape padding to block multiples, interpret-mode fallback on CPU
(this container validates kernels with interpret=True; on TPU the same
code path compiles to Mosaic), and custom VJPs (the backward of the
one-hot-matmul gather is the transposed one-hot matmul — a deterministic
scatter-add on the MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import cce_lookup as _cl
from repro.kernels import kmeans_assign as _ka


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --- cce_lookup ---------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _cce_lookup(runs: tuple, tables: jax.Array, b_blk: int, k_blk: int):
    return _cce_lookup_fwd(runs, tables, b_blk, k_blk)[0]


def _cols(runs) -> list[int]:
    """Each run's first column in the slab."""
    return [sum(r.shape[0] for r in runs[:i]) for i in range(len(runs))]


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _cce_lookup_fwd(runs, tables, b_blk, k_blk):
    c = tables.shape[0]
    B = runs[0].shape[1]
    _, _, k, dsub = tables.shape
    B_pad = _round_up(B, b_blk)
    k_pad = _round_up(k, k_blk)
    tab_p = jnp.swapaxes(
        jnp.pad(tables, ((0, 0), (0, 0), (0, k_pad - k), (0, 0))), 2, 3)
    out = _cat([
        _cl.cce_lookup_fwd_pallas(
            jnp.swapaxes(jnp.pad(r, ((0, 0), (0, B_pad - B), (0, 0))), 1, 2), tab_p,
            col0=c0, b_blk=b_blk, k_blk=k_blk, interpret=_on_cpu())
        for r, c0 in zip(runs, _cols(runs))
    ])  # (c, dsub, B_pad)
    out = jnp.transpose(out, (2, 0, 1))[:B].reshape(B, c * dsub)
    return out, (runs, k, tables.shape[1], jnp.zeros((0,), tables.dtype))


def _cce_lookup_bwd(b_blk, k_blk, res, g):
    runs, k, T, dtype_token = res
    tdtype = dtype_token.dtype
    B = runs[0].shape[1]
    c = sum(r.shape[0] for r in runs)
    dsub = g.shape[-1] // c
    B_pad = _round_up(B, b_blk)
    k_pad = _round_up(k, k_blk)
    g_p = jnp.pad(
        g.reshape(B, c, dsub).astype(tdtype), ((0, B_pad - B), (0, 0), (0, 0))
    )
    # padded batch rows all point at row 0 — mask their contribution by
    # zeroing the padded gradient rows (jnp.pad already zero-fills).
    g_t = jnp.transpose(g_p, (1, 2, 0))  # (c, dsub, B_pad)
    dtab = _cat([
        _cl.cce_lookup_bwd_pallas(
            jnp.swapaxes(jnp.pad(r, ((0, 0), (0, B_pad - B), (0, 0))), 1, 2), g_t,
            k_pad, T, col0=c0, b_blk=b_blk, k_blk=k_blk,
            interpret=_on_cpu())
        for r, c0 in zip(runs, _cols(runs))
    ])
    dtab = jnp.swapaxes(dtab, 2, 3)[:, :, :k, :]
    zero_idx = tuple(np.zeros(r.shape, jax.dtypes.float0) for r in runs)
    return (zero_idx, dtab)


_cce_lookup.defvjp(_cce_lookup_fwd, _cce_lookup_bwd)


def cce_lookup(
    idx,
    tables: jax.Array,
    *,
    b_blk: int = _cl.DEFAULT_B_BLK,
    k_blk: int | None = None,
) -> jax.Array:
    """Fused multi-table gather-sum: (c, B, T) idx + (c, T, k, dsub) tables
    -> (B, c*dsub) embeddings.  Differentiable w.r.t. ``tables``.

    Table-count-generic (any T) with the -1 no-op row sentinel (zero
    forward contribution, zero gradient) — the universal-fusion contract
    (see kernels/cce_lookup.py and DESIGN.md §6).

    Sum-pooled bags: ``idx`` may instead be a sequence of RUNS of adjacent
    columns, each (c_r, B, bag_r * T) with its own bag length (slot ``l *
    T + t``: the bag's l-th id in sub-table t), the c_r summing to the
    slab's c; a column's output is the sum of its bag's rows.  One launch
    per run, forward and backward."""
    runs = (idx,) if hasattr(idx, "shape") else tuple(idx)
    k = tables.shape[2]
    if k_blk is None:
        k_blk = min(_cl.DEFAULT_K_BLK, _round_up(k, 128))
    b_blk = min(b_blk, _round_up(runs[0].shape[1], 8))
    return _cce_lookup(runs, tables, b_blk, k_blk)


def pad_stack_tables(slabs, *, k_pad: int | None = None) -> jax.Array:
    """Ragged group stacking for the ``EmbeddingCollection`` supertable.

    Per-feature table slabs (c_f, T, k_f, dsub) — same T/dsub, ragged
    codebook size k_f — concatenate along columns into a single
    (sum c_f, T, max k_f, dsub) supertable, zero-padding the codebook
    axis.  The contract that makes the padding free: row ids into column
    f are always < k_f (learned pointers and helper hashes are both
    mod-k_f), so padded rows are never touched by the forward one-hot
    and receive exactly-zero gradient from the backward scatter-add.
    ``cce_lookup`` then pads max k_f up to the k_blk multiple on top.
    """
    k_pad = k_pad or max(s.shape[2] for s in slabs)
    return jnp.concatenate(
        [
            jnp.pad(s, ((0, 0), (0, 0), (0, k_pad - s.shape[2]), (0, 0)))
            for s in slabs
        ],
        axis=0,
    )


# --- flash attention ----------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool = True,
                    bq: int | None = None, bk: int | None = None):
    """Pallas flash attention (see kernels/flash_attention.py).  q (B,Sq,H,D),
    k/v (B,S,KVH,D) -> (B,Sq,H,D).  Pads Sq/S to block multiples."""
    from repro.kernels import flash_attention as _fa

    B, Sq, H, D = q.shape
    S = k.shape[1]
    bq = bq or min(_fa.DEFAULT_BQ, _round_up(Sq, 128))
    bk = bk or min(_fa.DEFAULT_BK, _round_up(S, 128))
    Sq_p, S_p = _round_up(Sq, bq), _round_up(S, bk)
    q_p = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    # padded kv rows must never win the softmax: causal masking already
    # excludes them for q < Sq when S_p == Sq_p (the causal contract here)
    out = _fa.flash_attention_pallas(
        q_p, k_p, v_p, causal=causal, bq=bq, bk=bk, interpret=_on_cpu()
    )
    return out[:, :Sq]


# --- kmeans_assign ------------------------------------------------------------

_PAD_CENTROID = 1e15  # ||pad||^2 ~ 1e30 * d — never the argmin, no inf-inf NaNs


def kmeans_assign(
    x: jax.Array,
    centroids: jax.Array,
    *,
    n_blk: int = _ka.DEFAULT_N_BLK,
    k_blk: int | None = None,
) -> jax.Array:
    """(n, d) points, (k, d) centroids -> (n,) int32 nearest-centroid ids."""
    n, d = x.shape
    k = centroids.shape[0]
    if k_blk is None:
        k_blk = min(_ka.DEFAULT_K_BLK, _round_up(k, 128))
    n_blk = min(n_blk, _round_up(n, 8))
    n_pad = _round_up(n, n_blk)
    k_pad = _round_up(k, k_blk)
    x_p = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    c_p = jnp.pad(
        centroids, ((0, k_pad - k), (0, 0)), constant_values=_PAD_CENTROID
    )
    arg, _ = _ka.kmeans_assign_pallas(
        x_p, c_p, n_blk=n_blk, k_blk=k_blk, interpret=_on_cpu()
    )
    return arg[:n]
