"""Plain float32 DLRM-DCNv2 (MLPerf Training ``recommendation_v2``,
torchrec ``DLRM_DCN``; Wang et al. 2021) with CCE or full embedding tables
and sum-pooled multi-hot bags: the reference that decides ``correct`` for
``dlrm_dcnv2_criteo_1tb``.  Straightforward ``jax.numpy``: gathers summed
over each bag, dense layers and the cross layers, no kernel, one-hot
product or padding.  It reads the benchmark's per-feature weights
(``harness/weights.py``), makes the dense ones itself (``make_dense``),
trains with Adagrad without a clip (``init_opt``, ``train_step``) and
imports nothing of the program; the hash, precision and sketch helpers are
``dlrm_reference``'s.

The model, as torchrec's ``DenseArch``, ``LowRankCrossNet`` and
``OverArch``: ``x0 = concat(dense_arch(dense), pooled embeddings)``, ReLU
after every dense-arch layer; ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) +
x_l`` with ``V_l`` to the rank without bias and ``W_l`` back with bias;
the over arch with ReLU after every layer but the last; BCE on the logit.

Bags: feature f's ids are the sparse columns from ``sum(bag_sizes[:f])``
on, and its embedding is the sum of its ids' embeddings, a CCE id's being
``concat_i(M_i[ptr_i[id]] + M'_i[h'_i(id)])``.  Every id of a tracked
feature's bag counts in its sketch cells and its exact counts.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "dcnv2_dlrm_reference", Path(__file__).resolve().parent / "dlrm_reference.py")
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

product, helper_hash, sketch_coeffs = _base.product, _base.helper_hash, _base.sketch_coeffs


def bag_starts(cfg: dict) -> np.ndarray:
    """(n_features + 1,): feature f's bag is columns ``[s[f], s[f + 1])``."""
    return np.cumsum([0] + list(cfg["bag_sizes"]))


def embed(emb_params, emb_buffers, sparse, cfg: dict):
    """(B, sum(bag_sizes)) ids -> (B, F, d2), each the sum over its bag."""
    s = bag_starts(cfg)
    outs = []
    for f, (p, bufs) in enumerate(zip(emb_params, emb_buffers)):
        ids = sparse[:, s[f] : s[f + 1]]  # (B, bag)
        if "table" in p:
            outs.append(p["table"][ids].sum(axis=1))
            continue
        tabs = p["tables"]  # (c, 2, k, dsub)
        c, _, k, _ = tabs.shape
        cols = []
        for i in range(c):
            main = tabs[i, 0][bufs["ptr"][i][ids]]
            helper = tabs[i, 1][helper_hash(ids, bufs["hs"][i, 0], bufs["hs"][i, 1], k)]
            cols.append((main + helper).sum(axis=1))
        outs.append(jnp.concatenate(cols, axis=-1))
    return jnp.stack(outs, axis=1)


def _linear(layer, x, precision: str):
    return product("bi,io->bo", x, layer["w"], precision) + layer["b"]


def _init_linear(key, a: int, b: int, bias: bool = True):
    w = jax.random.normal(key, (a, b), jnp.float32) / math.sqrt(a)
    return {"w": w, "b": jnp.zeros((b,), jnp.float32)} if bias else {"w": w}


def make_dense(cfg: dict, key):
    """The dense arch, the cross layers and the over arch, from the first
    two of the three keys split from ``key`` (the embedding tables take the
    third): weights normal over the square root of fan-in, biases zero.
    A cross layer is ``{"v": (width, rank), "w": (rank, width), "b":
    (width,)}``, the program's layout."""
    kb, kt, _ = jax.random.split(key, 3)
    kc, ko = jax.random.split(kt)
    width = cfg["bottom_mlp"][-1] + len(cfg["vocab_sizes"]) * cfg["emb_dim"]
    rank = cfg["cross_rank"]

    def mlp(k, sizes):
        return [_init_linear(jax.random.fold_in(k, i), a, b)
                for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))]

    cross = []
    for i in range(cfg["cross_layers"]):
        kv, kw = jax.random.split(jax.random.fold_in(kc, i))
        cross.append({"v": _init_linear(kv, width, rank, bias=False)["w"],
                      **_init_linear(kw, rank, width)})
    return {"bottom": mlp(kb, (cfg["n_dense"], *cfg["bottom_mlp"])), "cross": cross,
            "top": mlp(ko, (width, *cfg["top_mlp"]))}


def logits(params, emb_buffers, dense, sparse, cfg: dict, precision: str = "highest"):
    x = dense
    for layer in params["bottom"]:
        x = jax.nn.relu(_linear(layer, x, precision))
    e = embed(params["emb"], emb_buffers, sparse, cfg)
    x0 = jnp.concatenate([x, e.reshape(e.shape[0], -1)], axis=-1)
    x = x0
    for layer in params["cross"]:
        low = product("bi,ir->br", x, layer["v"], precision)
        x = x0 * (_linear(layer, low, precision)) + x
    for i, layer in enumerate(params["top"]):
        x = _linear(layer, x, precision)
        if i < len(params["top"]) - 1:
            x = jax.nn.relu(x)
    return x[:, 0]


def bce(params, emb_buffers, batch, cfg: dict, precision: str = "highest"):
    z = logits(params, emb_buffers, batch["dense"], batch["sparse"], cfg, precision)
    y = batch["label"]
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def init_opt(params, cfg: dict):
    """Adagrad's accumulators, one per parameter, at the initial value;
    this reference trains nothing else, and clips nothing."""
    opt = cfg["optimizer"]
    if opt["name"] != "adagrad" or opt.get("clip_norm") is not None:
        raise ValueError(f"dlrm_dcnv2_reference trains Adagrad without a clip, not {opt}")
    a0 = float(opt.get("initial_accumulator", 0.0))
    return jax.tree.map(lambda p: jnp.full_like(p, a0), params)


def train_step(params, acc, emb_buffers, batch, cfg: dict, precision: str = "highest"):
    """One Adagrad step, as ``torch.optim.Adagrad`` with no decay:
    ``a += g**2; p -= lr * g / (sqrt(a) + eps)``; (params, acc, loss)."""
    opt = cfg["optimizer"]
    loss, g = jax.value_and_grad(bce)(params, emb_buffers, batch, cfg, precision)
    acc = jax.tree.map(lambda a, x: a + x * x, acc, g)
    params = jax.tree.map(lambda p, x, a: p - opt["lr"] * x / (jnp.sqrt(a) + opt["eps"]),
                          params, g, acc)
    return params, acc, loss


def _feature_ids(sparse, f: int, cfg: dict) -> np.ndarray:
    s = bag_starts(cfg)
    return np.asarray(sparse)[:, s[f] : s[f + 1]].reshape(-1)


def sketch_delta(sparse, features, cfg: dict) -> np.ndarray:
    """(len(features), depth, width) int64: every id of each feature's bags
    counted once into each hash row's cell ``(a * id + b mod 2**32) >>
    (32 - log2 width)``, with ``cfg["stream"]``'s width, depth and seed."""
    stream = cfg["stream"]
    width, depth = int(stream["width"]), int(stream["depth"])
    shift = np.uint32(32 - (width.bit_length() - 1))
    out = np.zeros((len(features), depth, width), np.int64)
    for j, f in enumerate(features):
        a, b = sketch_coeffs(int(stream.get("seed", 0)), f, depth)
        x = _feature_ids(sparse, f, cfg).astype(np.uint32)
        for r in range(depth):
            out[j, r] = np.bincount((a[r] * x + b[r]) >> shift, minlength=width)
    return out


def fed_counts(pool, n_fed: int, features, cfg: dict) -> dict:
    """Exact counts of the ids fed by ``n_fed`` batches cycled from
    ``pool``, every id of a bag counted, each batch's count decayed by
    ``decay`` once for every window of ``window`` batches that closed
    after it: {feature: (ids, counts)}, heaviest first (ties by id).  Only
    the ids at least as heavy as the ``head_check_top + 1``-th heaviest
    are listed, all that the head check reads; a feature's counts are one
    weighted ``bincount`` over its vocabulary."""
    stream = cfg["stream"]
    window, decay = int(stream.get("window", 0)), float(stream.get("decay", 1.0))
    top = int(cfg["head_check_top"])
    t = np.arange(n_fed)
    closes = n_fed // window - t // window if window else np.zeros(n_fed, np.int64)
    w = np.power(decay, closes.astype(np.float64))
    per_batch = np.bincount(t % len(pool), weights=w, minlength=len(pool))
    fed = np.flatnonzero(per_batch)
    out = {}
    for f in features:
        ids = np.concatenate([_feature_ids(pool[i]["sparse"], f, cfg) for i in fed])
        wts = np.repeat(per_batch[fed], ids.size // len(fed))
        counts = np.bincount(ids, weights=wts, minlength=int(cfg["vocab_sizes"][f]))
        nz = np.flatnonzero(counts)
        k = min(top, nz.size - 1)
        edge = -np.partition(-counts[nz], k)[k]  # the (top + 1)-th heaviest
        keep = nz[counts[nz] >= edge]
        order = keep[np.argsort(-counts[keep], kind="stable")]
        out[f] = (order, counts[order])
    return out
