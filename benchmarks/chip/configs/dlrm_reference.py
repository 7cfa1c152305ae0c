"""Plain float32 DLRM (Naumov et al. 2019) with CCE or full embedding
tables: the reference that decides ``correct`` for the ``dlrm_*``
configurations.  Straightforward ``jax.numpy``: gathers, an einsum and
dense layers, no kernel, cache, padding or batching trick.  It reads the
benchmark's per-feature weights (``harness/weights.py``), makes the dense
ones itself (``make_dense``), trains with plain SGD (``init_opt``,
``train_step``) and imports nothing of the program.

CCE (the paper's Algorithm 3): ``emb(id) = concat_i(M_i[ptr_i[id]] +
M'_i[h'_i(id)])`` with ``h'_i`` a multiply-shift hash over the ``k`` helper
rows, coefficients ``hs[i] = (a, b)``.  A full table is ``T[id]``.

The id statistics a CCE table is clustered from, in plain numpy: each
tracked feature's count-min cells of a batch (``sketch_delta``) and the
exact, window-decayed count of every id a stream fed (``fed_counts``), what
a SpaceSaving head of the stream's heaviest ids must show.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: bf16 passes of a float32 product: "highest" is exact float32 (the
#: harness runs at that default), "high" three passes (hi*hi + hi*lo +
#: lo*hi, as the TPU computes Precision.HIGH), "bfloat16" one pass.
PASSES = {"highest": 0, "high": 3, "bfloat16": 1}


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def product(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at ``precision``, emulated with explicit
    bfloat16 splits so that it means the same on any backend."""
    passes = PASSES[precision]
    if passes == 0:
        return jnp.einsum(spec, a, b)
    (ah, al), (bh, bl) = _split(a), _split(b)
    out = jnp.einsum(spec, ah, bh)
    if passes == 3:
        out = out + jnp.einsum(spec, ah, bl) + jnp.einsum(spec, al, bh)
    return out


def helper_hash(ids, a, b, k: int):
    """Multiply-shift with a xor-shift mix, in wrapping uint32 arithmetic."""
    h = ids.astype(jnp.uint32) * a + b
    h = (h ^ (h >> 15)) * jnp.uint32(2654435761)
    h = h ^ (h >> 13)
    return (h % jnp.uint32(k)).astype(jnp.int32)


def embed(emb_params, emb_buffers, sparse):
    """(B, F) ids -> (B, F, d2)."""
    outs = []
    for f, (p, bufs) in enumerate(zip(emb_params, emb_buffers)):
        ids = sparse[:, f]
        if "table" in p:
            outs.append(p["table"][ids])
            continue
        tabs = p["tables"]  # (c, 2, k, dsub)
        c, _, k, _ = tabs.shape
        cols = []
        for i in range(c):
            main = tabs[i, 0][bufs["ptr"][i][ids]]
            helper = tabs[i, 1][helper_hash(ids, bufs["hs"][i, 0], bufs["hs"][i, 1], k)]
            cols.append(main + helper)
        outs.append(jnp.concatenate(cols, axis=-1))
    return jnp.stack(outs, axis=1)


def _mlp(layers, x, relu_last: bool, precision: str):
    for i, layer in enumerate(layers):
        x = product("bi,io->bo", x, layer["w"], precision) + layer["b"]
        if relu_last or i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _init_mlp(key, sizes):
    layers = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        w = jax.random.normal(jax.random.fold_in(key, i), (a, b), jnp.float32)
        layers.append({"w": w / math.sqrt(a), "b": jnp.zeros((b,), jnp.float32)})
    return layers


def make_dense(cfg: dict, key):
    """The bottom and top MLPs, from the first two of the three keys split
    from ``key`` (the embedding tables take the third): weights normal over
    the square root of fan-in, biases zero.  The top MLP reads the bottom's
    output beside the upper triangle of the pairwise interaction."""
    kb, kt, _ = jax.random.split(key, 3)
    n = len(cfg["vocab_sizes"])
    bottom = (cfg["n_dense"], *cfg["bottom_mlp"])
    top = (cfg["bottom_mlp"][-1] + (n + 1) * n // 2, *cfg["top_mlp"])
    return {"bottom": _init_mlp(kb, bottom), "top": _init_mlp(kt, top)}


def logits(params, emb_buffers, dense, sparse, precision: str = "highest"):
    x0 = _mlp(params["bottom"], dense, True, precision)  # (B, d2)
    v = jnp.concatenate([x0[:, None, :], embed(params["emb"], emb_buffers, sparse)], axis=1)
    inter = product("bie,bje->bij", v, v, precision)
    iu, ju = jnp.triu_indices(v.shape[1], k=1)
    feats = jnp.concatenate([x0, inter[:, iu, ju]], axis=-1)
    return _mlp(params["top"], feats, False, precision)[:, 0]


def bce(params, emb_buffers, batch, precision: str = "highest"):
    z = logits(params, emb_buffers, batch["dense"], batch["sparse"], precision)
    y = batch["label"]
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def clip(grads, clip_norm: float):
    """The gradient scaled down to a global norm of at most ``clip_norm``."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(grads)))
    return jax.tree.map(
        lambda x: x * jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12)), grads)


def init_opt(params, cfg: dict):
    """The optimizer's state: plain SGD keeps none, and this reference
    trains nothing else."""
    opt = cfg["optimizer"]
    if opt["name"] != "sgd" or opt.get("momentum", 0.0) != 0.0:
        raise ValueError(f"dlrm_reference trains plain SGD, not {opt}")
    return {}


def train_step(params, opt_state, emb_buffers, batch, cfg: dict, precision: str = "highest"):
    """One SGD step with the gradient clipped to a global norm:
    (new params, new optimizer state, loss)."""
    opt = cfg["optimizer"]
    loss, g = jax.value_and_grad(bce)(params, emb_buffers, batch, precision)
    g = clip(g, opt["clip_norm"])
    return jax.tree.map(lambda p, x: p - opt["lr"] * x, params, g), opt_state, loss


def sketch_coeffs(stream_seed: int, feature: int, depth: int):
    """The count-min hash rows of one feature: an odd multiplier and an
    offset per row, drawn by numpy's default generator seeded with
    ``stream_seed * 1_000_003 + feature``."""
    rng = np.random.default_rng(stream_seed * 1_000_003 + feature)
    a = rng.integers(0, 2**32, depth, dtype=np.uint32) | np.uint32(1)
    b = rng.integers(0, 2**32, depth, dtype=np.uint32)
    return a, b


def sketch_delta(sparse, features, cfg: dict) -> np.ndarray:
    """(len(features), depth, width) int64: every id of the batch counted
    once into each hash row's cell ``(a * id + b mod 2**32) >> (32 -
    log2 width)``, with ``cfg["stream"]``'s width, depth and seed."""
    stream = cfg["stream"]
    width, depth = int(stream["width"]), int(stream["depth"])
    shift = np.uint32(32 - (width.bit_length() - 1))
    sparse = np.asarray(sparse)
    out = np.zeros((len(features), depth, width), np.int64)
    for j, f in enumerate(features):
        a, b = sketch_coeffs(int(stream.get("seed", 0)), f, depth)
        x = sparse[:, f].astype(np.uint32)
        for r in range(depth):
            out[j, r] = np.bincount((a[r] * x + b[r]) >> shift, minlength=width)
    return out


def fed_counts(pool, n_fed: int, features, cfg: dict) -> dict:
    """Exact counts of the ids fed by ``n_fed`` batches cycled from
    ``pool``, each batch's count decayed by ``decay`` once for every window
    of ``window`` batches that closed after it: {feature: (ids, counts)},
    heaviest first; ``window`` and ``decay`` are ``cfg["stream"]``'s."""
    stream = cfg["stream"]
    window, decay = int(stream.get("window", 0)), float(stream.get("decay", 1.0))
    t = np.arange(n_fed)
    closes = n_fed // window - t // window if window else np.zeros(n_fed, np.int64)
    w = np.power(decay, closes.astype(np.float64))
    per_batch = np.bincount(t % len(pool), weights=w, minlength=len(pool))
    out = {}
    for f in features:
        ids = np.concatenate([np.asarray(b["sparse"])[:, f] for b in pool])
        wts = np.repeat(per_batch, [len(b["sparse"]) for b in pool])
        uids, inv = np.unique(ids, return_inverse=True)
        counts = np.bincount(inv, weights=wts, minlength=len(uids))
        order = np.argsort(-counts, kind="stable")
        out[f] = (uids[order], counts[order])
    return out
