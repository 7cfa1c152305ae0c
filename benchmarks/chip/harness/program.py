"""The system under test, built from a configuration file through the
program's own constructors and entry-point pieces (``launch/train.py``),
and handed the benchmark's weights."""
from __future__ import annotations

import argparse

import jax

from harness import weights as W


def dlrm_config(cfg: dict):
    from repro.models.dlrm import DLRMConfig

    return DLRMConfig(
        vocab_sizes=tuple(cfg["vocab_sizes"]), n_dense=cfg["n_dense"],
        emb_dim=cfg["emb_dim"], bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), emb_method=cfg["emb_method"],
        emb_param_cap=cfg["emb_param_cap"], emb_c=cfg["emb_c"],
        emb_fuse=cfg.get("emb_fuse", "univ"),
    )


def check_tables(pcfg, shapes) -> None:
    """The program's tables are the ones the benchmark made weights for."""
    from repro.core.cce import CCE

    for f, (t, s) in enumerate(zip(pcfg.collection.tables, shapes)):
        got = ("cce", t.d1, t.d2, t.k, t.c) if isinstance(t, CCE) else (
            "full", t.d1, t.d2, 0, 0)
        if got != (s.kind, s.d1, s.d2, s.k, s.c):
            raise ValueError(f"feature {f}: program table {got} != benchmark {s}")


def to_program(pcfg, params, bufs):
    """Per-feature benchmark weights -> the program's (params, buffers),
    through the program's own grouped-layout conversion."""
    coll = pcfg.collection
    stack = jax.jit(coll.stack_params)
    prog = {"bottom": params["bottom"], "top": params["top"],
            "emb": stack(params["emb"])}
    return prog, {"emb": coll.stack_buffers(bufs)}


def per_feature(pcfg, prog_params):
    """The program's params in the benchmark's per-feature layout."""
    return {"bottom": prog_params["bottom"], "top": prog_params["top"],
            "emb": pcfg.collection.unstack_params(prog_params["emb"])}


def tracker(pcfg, cfg: dict):
    """The sketch tracker the entry point builds at full width (None for
    a configuration without CCE tables)."""
    from repro.models import dlrm
    from repro.stream import StreamConfig

    if cfg["emb_method"] != "cce" or not any(
            s.kind == "cce" for s in W.table_shapes(cfg)):
        return None
    return dlrm.make_id_tracker(pcfg, StreamConfig(**cfg["stream"]))


def train_step(pcfg, cfg: dict, static, trk):
    """The entry point's donated one-device step and its optimizer."""
    from repro.launch.train import dlrm_train_step

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"the DLRM entry point trains with SGD, not {opt['name']!r}")
    args = argparse.Namespace(lr=opt["lr"], momentum=opt["momentum"], accum=1)
    return dlrm_train_step(pcfg, args, static, trk)
