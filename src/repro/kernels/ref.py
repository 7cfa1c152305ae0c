"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cce_lookup_ref(idx, tables: jax.Array) -> jax.Array:
    """Reference for the fused multi-column gather-sum.

    Args:
      idx:    (c, B, T) int32 — per column, per batch element, T row indices
              (T=2 for CCE main+helper, T=1 for plain CE-concat / hashed /
              full tables).  A NEGATIVE index is the sentinel for "no
              sub-table here" (a T=1 method riding a T=2 supertable): it
              matches no one-hot lane, so it contributes exactly zero
              forward and receives exactly zero gradient.
      tables: (c, T, k, dsub) — per column, T tables of k rows.

    Returns:
      (B, c * dsub): concat over columns of sum over tables of gathered rows.

    ``idx`` may instead be a sequence of runs of adjacent columns, each
    (c_r, B, bag * T): a column's output is then the sum over its bag of
    the one-id lookups, slot ``l * T + t`` being id l's row in table t.
    """
    if not hasattr(idx, "shape"):
        T, outs, c0 = tables.shape[1], [], 0
        for r in idx:
            tab = tables[c0 : c0 + r.shape[0]]
            outs.append(sum(cce_lookup_ref(r[..., s : s + T], tab)
                            for s in range(0, r.shape[2], T)))
            c0 += r.shape[0]
        return jnp.concatenate(outs, axis=1)
    c, B, T = idx.shape
    _, _, k, dsub = tables.shape
    # out[i, b] = sum_t [idx >= 0] * tables[i, t, idx[i, b, t]]
    gathered = jax.vmap(  # over columns
        lambda ti, ii: sum(
            ti[t][jnp.maximum(ii[:, t], 0)] * (ii[:, t] >= 0)[:, None].astype(ti.dtype)
            for t in range(T)
        )
    )(tables, idx)  # (c, B, dsub)
    return jnp.transpose(gathered, (1, 0, 2)).reshape(B, c * dsub)


def kmeans_assign_ref(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Reference nearest-centroid assignment.

    Args:
      x: (n, d); centroids: (k, d).
    Returns:
      (n,) int32 argmin_j ||x - c_j||^2  (ties -> lowest index).
    """
    x = x.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = (
        jnp.sum(x * x, -1, keepdims=True)
        + jnp.sum(c * c, -1)[None, :]
        - 2.0 * x @ c.T
    )
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jax.Array:
    """Reference for the flash-attention kernel: dense causal GQA SDPA.

    q (B, Sq, H, D); k/v (B, S, KVH, D) -> (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    S, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    k = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) / (D ** 0.5)
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).astype(q.dtype)


def cce_logits_ref(h: jax.Array, idx: jax.Array, tables: jax.Array) -> jax.Array:
    """Reference for the factored CCE logits head (beyond-paper extension).

    logits[b, v] = <h[b], E[v]> where E[v] = concat_i sum_t tables[i,t,idx[i,v,t]].

    Args:
      h:      (B, c * dsub) activations.
      idx:    (c, V, T) pointer arrays over the vocab.
      tables: (c, T, k, dsub).
    Returns:
      (B, V) logits.
    """
    c, V, T = idx.shape
    _, _, k, dsub = tables.shape
    B = h.shape[0]
    hc = h.reshape(B, c, dsub)
    out = jnp.zeros((B, V), jnp.float32)
    for i in range(c):
        scores = hc[:, i].astype(jnp.float32) @ tables[i].astype(jnp.float32).reshape(
            T * k, dsub
        ).T  # (B, T*k)
        for t in range(T):
            out = out + scores[:, t * k : (t + 1) * k][:, idx[i, :, t]]
    return out
