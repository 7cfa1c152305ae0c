"""The benchmark's weights, made from the seed on the device in one jitted
call, in a per-feature layout that the plain reference reads directly and
that the program receives through its own layout conversion.

A feature whose full table fits the configuration's parameter cap stays a
full table; a larger one is a CCE table with ``c`` columns of a main and a
helper codebook of ``k`` rows each (the paper's rule, Sec. 4.1).  CCE
pointers are uniform over the codebook rows, as after a clustering; the
helper hash coefficients are random 32-bit pairs with an odd multiplier.
The embedding tables take the third of the three keys split from the
seed's; a reference's dense parameters may take the other two.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TableShape:
    kind: str  # "cce" | "full"
    d1: int
    d2: int
    k: int = 0
    c: int = 0


def table_shapes(cfg: dict) -> tuple[TableShape, ...]:
    d2, cap, method = cfg["emb_dim"], cfg["emb_param_cap"], cfg["emb_method"]
    out = []
    for v in cfg["vocab_sizes"]:
        if method == "full" or not cap or v * d2 <= cap:
            out.append(TableShape("full", v, d2))
        elif method == "cce":
            c = cfg["emb_c"]
            out.append(TableShape("cce", v, d2, k=max(1, min(v, cap // (2 * d2))), c=c))
        else:
            raise ValueError(f"no benchmark weights for emb_method {method!r}")
    return tuple(out)


def make_fn(cfg: dict, ref):
    """Two jitted functions of the key: ``params`` (a per-feature ``emb``
    list beside the dense parameters that the configuration's reference
    module makes, ``ref.make_dense(cfg, key)``, when it defines it) and
    ``buffers`` (one dict per feature).  Each is one program, so every
    caller of one gets the same bits."""
    shapes = table_shapes(cfg)
    make_dense = getattr(ref, "make_dense", None)

    def make(key):
        ke = jax.random.split(key, 3)[2]
        emb, bufs = [], []
        for f, s in enumerate(shapes):
            kf = jax.random.fold_in(ke, f)
            scale = 1.0 / math.sqrt(s.d2)
            if s.kind == "full":
                emb.append({"table": jax.random.normal(kf, (s.d1, s.d2), jnp.float32) * scale})
                bufs.append({})
                continue
            kt_, kp, kh = jax.random.split(kf, 3)
            dsub = s.d2 // s.c
            emb.append({"tables": jax.random.normal(kt_, (s.c, 2, s.k, dsub), jnp.float32) * scale})
            hs = jax.random.bits(kh, (s.c, 2), jnp.uint32)
            bufs.append({
                "ptr": jax.random.randint(kp, (s.c, s.d1), 0, s.k, jnp.int32),
                "hs": hs.at[:, 0].set(hs[:, 0] | jnp.uint32(1)),
                "epoch": jnp.zeros((), jnp.int32),
            })
        dense = make_dense(cfg, key) if make_dense is not None else {}
        return {**dense, "emb": emb}, bufs

    return jax.jit(lambda k: make(k)[0]), jax.jit(lambda k: make(k)[1])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one above 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
