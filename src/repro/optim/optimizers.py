"""Optimizers as pure (init, update) pairs over param pytrees.

SGD(+momentum) is the paper's choice for DLRM; Adagrad is MLPerf
DLRM-DCNv2's; AdamW is the LM default.
ZeRO-1: `zero1_specs` extends a parameter PartitionSpec tree so optimizer
moments are additionally sharded over the data axis wherever a dimension
is divisible — optimizer state then costs 1/(dp·tp) per device while the
params keep their own layout (XLA inserts the gather on use; with the
moments only read once per step this is the standard ZeRO-1 trade).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Pytree], Pytree]
    update: Callable[[Pytree, Pytree, Pytree, jax.Array], tuple[Pytree, Pytree]]
    # update(grads, state, params, lr) -> (new_params, new_state)


def sgd(momentum: float = 0.0, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return {}
        return {"m": jax.tree.map(jnp.zeros_like, params)}

    def update(grads, state, params, lr):
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
        if momentum == 0.0:
            new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new_params, state
        m = jax.tree.map(lambda m, g: momentum * m + g, state["m"], grads)
        new_params = jax.tree.map(lambda p, m_: p - lr * m_, params, m)
        return new_params, {"m": m}

    return Optimizer(init, update)


def adagrad(eps: float = 1e-8, initial_accumulator: float = 0.0) -> Optimizer:
    """Adagrad (Duchi et al. 2011) as PyTorch's ``torch.optim.Adagrad``
    computes it with no learning-rate decay, which MLPerf DLRM-DCNv2
    trains with: ``a += g**2; p -= lr * g / (sqrt(a) + eps)``, element-wise
    on every leaf, embedding tables included."""

    def init(params):
        return {"a": jax.tree.map(lambda p: jnp.full_like(p, initial_accumulator), params)}

    def update(grads, state, params, lr):
        a = jax.tree.map(lambda a_, g: a_ + g * g, state["a"], grads)
        new_params = jax.tree.map(
            lambda p, g, a_: (p - lr * g / (jnp.sqrt(a_) + eps)).astype(p.dtype),
            params, grads, a)
        return new_params, {"a": a}

    return Optimizer(init, update)


def adamw(
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
) -> Optimizer:
    def init(params):
        return {
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params, lr):
        t = state["t"] + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def step(p, m_, v_):
            upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            return (p - lr * (upd + weight_decay * p)).astype(p.dtype)

        new_params = jax.tree.map(step, params, m, v)
        return new_params, {"m": m, "v": v, "t": t}

    return Optimizer(init, update)


def clip_by_global_norm(grads: Pytree, max_norm: float) -> tuple[Pytree, jax.Array]:
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), gnorm


def cosine_schedule(base_lr: float, warmup: int, total: int, min_frac: float = 0.1):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(1, warmup)
        prog = jnp.clip((step - warmup) / max(1, total - warmup), 0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def zero1_specs(param_specs: Pytree, params_shape: Pytree, dp_axis: str = "data",
                dp_size: int = 0) -> Pytree:
    """PartitionSpec tree for optimizer moments: start from the param spec
    and additionally shard the largest unsharded dim over ``dp_axis`` where
    divisible by ``dp_size`` (ZeRO-1).  ``params_shape``: matching tree of
    jax.ShapeDtypeStruct (or arrays)."""

    def extend(spec, leaf):
        if not isinstance(spec, P):
            return spec
        shape = leaf.shape
        entries = list(spec) + [None] * (len(shape) - len(spec))
        used = {a for e in entries if e for a in ((e,) if isinstance(e, str) else e)}
        if dp_axis in used:  # EP-style params already consume the dp axis
            return spec
        best, best_dim = -1, -1
        for i, (s, e) in enumerate(zip(shape, entries)):
            if e is None and dp_size and s % dp_size == 0 and s > best:
                best, best_dim = s, i
        if best_dim >= 0:
            entries[best_dim] = dp_axis
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree.map(
        extend, param_specs, params_shape,
        is_leaf=lambda x: isinstance(x, P),
    )


def moment_specs(opt_name: str, param_specs: Pytree, params_shape: Pytree,
                 dp_axis: str = "data", dp_size: int = 0) -> Pytree:
    """Spec tree matching the optimizer *state* structure."""
    z = zero1_specs(param_specs, params_shape, dp_axis, dp_size)
    if opt_name == "sgd":
        return {}
    if opt_name == "sgdm":
        return {"m": z}
    return {"m": z, "v": z, "t": P()}
