"""Test-only reference of ``seam_model.json``: the plain DLRM of
``configs/dlrm_reference.py`` with dense weights of its own (uniform,
with nonzero biases) and SGD with momentum, whose state it carries."""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "seam_dlrm_reference", Path(__file__).resolve().parents[1] / "configs" / "dlrm_reference.py")
_dlrm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dlrm)

bce, logits = _dlrm.bce, _dlrm.logits
sketch_delta, fed_counts = _dlrm.sketch_delta, _dlrm.fed_counts


def _uniform_mlp(key, sizes):
    layers = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        lim = 1.0 / math.sqrt(a)
        layers.append({"w": jax.random.uniform(kw, (a, b), jnp.float32, -lim, lim),
                       "b": jax.random.uniform(kb, (b,), jnp.float32, -lim, lim)})
    return layers


def make_dense(cfg: dict, key):
    kb, kt, _ = jax.random.split(key, 3)
    n = len(cfg["vocab_sizes"])
    bottom = (cfg["n_dense"], *cfg["bottom_mlp"])
    top = (cfg["bottom_mlp"][-1] + (n + 1) * n // 2, *cfg["top_mlp"])
    return {"bottom": _uniform_mlp(kb, bottom), "top": _uniform_mlp(kt, top)}


def init_opt(params, cfg: dict):
    assert cfg["optimizer"]["name"] == "sgd"
    return {"m": jax.tree.map(jnp.zeros_like, params)}


def train_step(params, opt_state, emb_buffers, batch, cfg: dict, precision: str = "highest"):
    opt = cfg["optimizer"]
    loss, g = jax.value_and_grad(bce)(params, emb_buffers, batch, precision)
    g = _dlrm.clip(g, opt["clip_norm"])
    m = jax.tree.map(lambda m, x: opt["momentum"] * m + x, opt_state["m"], g)
    return jax.tree.map(lambda p, v: p - opt["lr"] * v, params, m), {"m": m}, loss
