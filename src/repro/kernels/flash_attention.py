"""Flash attention (causal, GQA) as a Pallas TPU kernel.

The §Perf analysis (EXPERIMENTS.md) shows the dominant HBM-traffic term of
every *_train cell is the f32 S^2 score/softmax chain — ~3.2 TB/step/chip
on qwen3-14b train_4k, 40-50% of the memory roofline term.  XLA cannot fix
this: the online-softmax rewrite is not expressible as a fusion of the
dense graph (verified: a chunked lax.scan formulation still materializes
every per-chunk block at instruction boundaries).  A kernel is the
mechanism: scores live in VMEM registers only, HBM sees Q, K, V, O exactly
once.

Layout: grid (batch*q_heads, Sq/bq).  Per grid step the q block (bq, D)
and the FULL per-head K/V (S, D) are staged in VMEM (bf16 at S=32k, D=128:
8 MB both — within the 16 MB budget; longer sequences stream K/V with a
third grid axis).  The kv loop runs online softmax with f32 accumulators
in VMEM scratch.

Validated in interpret mode against ref.flash_attention_ref over
shape/dtype sweeps (tests/test_kernels_flash.py); on TPU the same
pallas_call compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import pallas as pl

DEFAULT_BQ = 512
DEFAULT_BK = 512


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, scale: float, causal: bool,
            q_offset_den: int):
    # q_ref (bq, D); k_ref/v_ref (S, D); o_ref (bq, D)
    bq, D = q_ref.shape
    S = k_ref.shape[0]
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]

    nk = S // bk

    def body(j, carry):
        acc, m, ell = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos[:, None] >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[:, None]), 0.0)
        ell = ell * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, m_new, ell

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # causal: kv blocks beyond this q block never contribute — bound the loop
    # (program_id is traced: ceil-div in lax arithmetic)
    hi = nk if not causal else jnp.minimum(((qi + 1) * bq + bk - 1) // bk, nk)
    acc, m, ell = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(ell, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """q (B, Sq, H, D); k/v (B, S, KVH, D) -> (B, Sq, H, D).

    GQA: query head h reads kv head h // (H // KVH).  Sq % bq == 0 and
    S % bk == 0 required (ops.flash_attention pads).
    """
    B, Sq, H, D = q.shape
    S, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    bq = min(bq, Sq)
    bk = min(bk, S)
    assert Sq % bq == 0 and S % bk == 0
    scale = 1.0 / (D ** 0.5)
    # (B*H, S, D) layouts; kv head index derived from the fused b*h axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, S, D)

    grid = (B * H, Sq // bq)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, scale=scale, causal=causal,
                          q_offset_den=bq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, S, D), lambda bh, i: (bh // G, 0, 0)),
            pl.BlockSpec((None, S, D), lambda bh, i: (bh // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
