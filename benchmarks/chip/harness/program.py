"""The system under test, built from a configuration file through the
program's own constructors and entry-point pieces (``launch/train.py``),
and handed the benchmark's weights."""
from __future__ import annotations

import argparse
import dataclasses
import inspect

import jax
import jax.numpy as jnp

from harness import weights as W

#: keys of a configuration file that the harness reads itself; every other
#: key names a field of the program's ``DLRMConfig``
HARNESS_KEYS = frozenset({
    "name", "source", "reference", "stream", "optimizer", "train_batch",
    "matmul_precision", "reduced", "assumed", "limits", "head_check_top", "bag_sizes",
})
HARNESS_PREFIXES = ("serve_",)


def dlrm_config(cfg: dict):
    """The program's ``DLRMConfig`` from every key of the configuration that
    names one of its fields: lists become tuples and ``dtype`` a JAX dtype.
    A key that is neither such a field nor the harness's is an error."""
    from repro.models.dlrm import DLRMConfig

    fields = {f.name for f in dataclasses.fields(DLRMConfig)}
    kw = {}
    for k, v in cfg.items():
        if k in fields:
            kw[k] = getattr(jnp, v) if k == "dtype" else tuple(v) if isinstance(v, list) else v
        elif k not in HARNESS_KEYS and not k.startswith(HARNESS_PREFIXES):
            raise ValueError(f"configuration {cfg.get('name')!r}: key {k!r} is neither a "
                             "DLRMConfig field nor one the benchmark reads")
    return DLRMConfig(**kw)


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
    through the program's own grouped-layout conversion; every dense
    parameter passes unchanged."""
    coll = pcfg.collection
    prog = {**params, "emb": jax.jit(coll.stack_params)(params["emb"])}
    return prog, {"emb": coll.stack_buffers(bufs)}


def per_feature(pcfg, prog_params):
    """The program's params in the benchmark's per-feature layout."""
    return {**prog_params, "emb": pcfg.collection.unstack_params(prog_params["emb"])}


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
    """The entry point's donated one-device step and its optimizer.  The
    whole ``optimizer`` block reaches the entry point: its ``name`` as
    ``args.optimizer``, every other key (``lr``, ``momentum``,
    ``clip_norm``, ...) under its own name, once ``check_optimizer`` has
    found that the entry point honours it."""
    from repro.launch import train as entry

    opt = cfg["optimizer"]
    check_optimizer(entry, cfg)
    args = argparse.Namespace(accum=1, **{"optimizer" if k == "name" else k: v
                                          for k, v in opt.items()})
    return entry.dlrm_train_step(pcfg, args, static, trk)


def check_optimizer(entry, cfg: dict) -> None:
    """Refuse an optimizer block that the entry point would not honour.
    An entry point that names the optimizers it picks by
    (``DLRM_OPTIMIZERS``) reads the name and ``clip_norm`` itself; one that
    names none trains SGD at ``make_train_step``'s default clip whatever it
    is handed, so any other name or clip is refused here, by name, and not
    left to the readings (PERF.md, section 7)."""
    from repro.train.loop import make_train_step

    opt = cfg["optimizer"]
    names = getattr(entry, "DLRM_OPTIMIZERS", None)
    if names is not None:
        if opt["name"] not in names:
            raise ValueError(f"configuration {cfg.get('name')!r}: the DLRM entry point "
                             f"trains {sorted(names)}, not {opt['name']!r}")
        return
    clip = inspect.signature(make_train_step).parameters["clip_norm"].default
    if opt["name"] != "sgd" or opt.get("clip_norm", clip) != clip:
        raise ValueError(
            f"configuration {cfg.get('name')!r}: the DLRM entry point reads neither the "
            f"optimizer's name nor its clip norm and trains SGD at clip_norm {clip}, not "
            f"{opt['name']!r} at clip_norm {opt.get('clip_norm', clip)} (PERF.md, section 7)")
