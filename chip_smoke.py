#!/usr/bin/env python3
"""Smoke test of the DLRM-CCE main path on the TPU at the published Criteo
widths (``configs.dlrm_criteo.CONFIG``: 26 Kaggle-vocabulary tables,
emb_dim 16, MLPs (512,256,64,16)/(512,256,1), CCE cap 8000).

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the 4-way sharded supertable

One chip, one process, three phases, all through the repo's own entry
points:

  (b) train through ``launch.train.build_dlrm_trainer`` (``--no-reduced``,
      batch 2048) with one clustering transition inside the run — the
      full-vocabulary ``assign_all`` runs through the ``kmeans_assign``
      kernel; loss finite before and after, ``ptr`` rewritten, ``epoch``
      advanced;
  (c) the fused ``cce_lookup`` kernel against the jnp gather path on the
      same device, parameters and batch, and the step's HLO carries the
      kernel (``tpu_custom_call``);
  (d) ``DLRMServeEngine`` fed the trained state and tracker serves
      batches of 256 through both its hit and its cold program, and the
      logits match ``dlrm.forward``.

``--four-chips`` runs only the sharded trainer (``--model-shards 4``:
a few steps plus one ``cluster_sharded`` transition) and its comparison
with the 1-device step on the same initial state and batch.

Figures printed on the way (compile, step and transition times, peak
device memory) are smoke figures from this one run, not benchmarks.  The
last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero without it.  Without a TPU it exits non-zero
before doing any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH = 2048
SERVE_BATCH = 256


def check_device(n_chips: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _block(tree):
    import jax

    jax.block_until_ready(tree)


def _timed(label, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    print(f"smoke figure: {label} {dt:.3f} s")
    return out


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _ptr_epoch(coll, state, f):
    import numpy as np

    fb = coll.feature_buffers(state.ebuf["emb"], f)
    return np.asarray(fb["ptr"]), int(fb["epoch"])


def _check_transition(coll, before, trainer, cce_feats):
    """One transition happened: every CCE table's epoch advanced by one
    and the checked table's pointers were rewritten."""
    import numpy as np

    if trainer.clusters_done != 1:
        raise AssertionError(f"expected 1 transition, got {trainer.clusters_done}")
    ptr0, _ = before
    ptr1, _ = _ptr_epoch(coll, trainer.state, cce_feats[0])
    if np.array_equal(ptr0, ptr1):
        raise AssertionError("transition left ptr unchanged")
    for f in cce_feats:
        _, ep = _ptr_epoch(coll, trainer.state, f)
        if ep != 1:
            raise AssertionError(f"feature {f}: epoch {ep} after one transition")


def _losses_finite(hist, label):
    import numpy as np

    losses = np.asarray([h["loss"] for h in hist], np.float64)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss {losses}")
    return losses


def train_phase(train_mod, cluster_at: int = 12, n_steps: int = 20):
    """(b): published-width training with one transition at ``cluster_at``."""
    from repro.core.cce import CCE

    args = train_mod.parse_args([
        "--arch", "dlrm", "--no-reduced", "--batch", str(BATCH),
        "--steps", str(n_steps), "--cluster-every", str(cluster_at),
    ])
    trainer = _timed("build trainer (init + host tables)",
                     lambda: train_mod.build_dlrm_trainer(args))
    cfg = train_mod.dlrm_config(args)
    coll = cfg.collection
    cce_feats = [i for i, t in enumerate(coll.tables) if isinstance(t, CCE)]
    print(f"config: {cfg.n_sparse} tables, supertable "
          f"{[tuple(p['tables'].shape) for p in trainer.state.params['emb']]}, "
          f"{len(cce_feats)} CCE tables, {cfg.n_emb_params()} emb params")
    before = _ptr_epoch(coll, trainer.state, cce_feats[0])

    def run(n):
        trainer.run(n)
        _block(trainer.state)

    _timed("first train step (incl. compile)", lambda: run(1))
    steady = cluster_at - 2
    t0 = time.perf_counter()
    run(steady)
    print(f"smoke figure: train step {(time.perf_counter() - t0) / steady:.4f} "
          f"s/step (batch {BATCH}, {steady} steps, host data generation included)")
    _timed("train step + clustering transition", lambda: run(1))
    _timed("first step after the transition", lambda: run(1))
    run(n_steps - cluster_at - 1)

    hist = list(trainer.history)
    losses = _losses_finite(hist, "train")
    _check_transition(coll, before, trainer, cce_feats)
    print(f"train: {len(hist)} steps, loss {losses[0]:.5f} -> "
          f"{losses[cluster_at - 1]:.5f} (transition) -> {losses[-1]:.5f}; "
          f"peak_bytes_in_use {_peak_bytes()}")
    return trainer, cfg


def kernel_phase(trainer, cfg):
    """(c): fused kernel == jnp gather on the same device and inputs."""
    import jax
    import numpy as np

    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.models import dlrm
    from repro.train.loop import merge_buffers

    params = trainer.state.params
    buffers = merge_buffers(trainer.state.ebuf, trainer.static_buffers)
    raw = next(clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=1), BATCH))
    batch = {k: v for k, v in raw.items() if k != "step"}
    coll = cfg.collection

    def emb(use_kernel):
        return jax.jit(lambda p, b, s: coll.lookup_all(
            p["emb"], b["emb"], s, use_kernel=use_kernel))(
            params, buffers, batch["sparse"])

    def loss(use_kernel):
        c = dataclasses.replace(cfg, emb_use_kernel=use_kernel)
        return float(jax.jit(lambda p, b, bt: dlrm.bce_loss(p, b, c, bt))(
            params, buffers, batch))

    e_k, e_j = np.asarray(emb(True)), np.asarray(emb(False))
    np.testing.assert_allclose(e_k, e_j, rtol=1e-6, atol=0)
    l_k, l_j = loss(True), loss(False)
    np.testing.assert_allclose(l_k, l_j, rtol=1e-5)
    hlo = trainer.train_step.lower(
        trainer.state, {k: np.asarray(v)[None] for k, v in batch.items()}
    ).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("train step HLO has no tpu_custom_call")
    print(f"kernel: embeddings {e_k.shape} max |kernel - jnp| "
          f"{float(np.abs(e_k - e_j).max())!r}; loss kernel {l_k!r} "
          f"jnp {l_j!r}; step HLO carries tpu_custom_call")


def serve_phase(trainer, cfg, n_cold: int = 3):
    """(d): serve the trained state; hit and cold programs both run and
    agree with the training forward."""
    import jax
    import numpy as np

    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.models import dlrm
    from repro.serve.dlrm import DLRMServeEngine
    from repro.train.loop import merge_buffers

    params = trainer.state.params
    buffers = merge_buffers(trainer.state.ebuf, trainer.static_buffers)
    engine = _timed("build serve engine (hot cache + host translator)",
                    lambda: DLRMServeEngine(params, buffers, cfg,
                                            tracker=trainer.id_tracker,
                                            max_batch=SERVE_BATCH))
    fwd = jax.jit(lambda p, b, bt: dlrm.forward(p, b, cfg, bt))
    missing = set(range(cfg.n_sparse)) - set(engine.cache.ids)
    if missing:
        raise AssertionError(f"hot cache covers no ids of features {missing}")

    rng = np.random.default_rng(3)
    hit_sparse = np.stack(
        [rng.choice(engine.cache.ids[f], SERVE_BATCH)
         for f in range(cfg.n_sparse)], axis=1).astype(np.int32)
    gen = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=2), SERVE_BATCH)
    batches = [(rng.normal(size=(SERVE_BATCH, cfg.n_dense)).astype(np.float32),
                hit_sparse)]
    batches += [(b["dense"], b["sparse"]) for b in itertools.islice(gen, n_cold)]

    worst, exact = 0.0, True
    for i, (dense, sparse) in enumerate(batches):
        t0 = time.perf_counter()
        got = engine.predict(dense, sparse)
        dt = time.perf_counter() - t0
        want = np.asarray(fwd(params, buffers, {"dense": dense, "sparse": sparse}))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        worst = max(worst, float(np.abs(got - want).max()))
        exact &= bool(np.array_equal(got, want))
        print(f"smoke figure: serve batch {i} ({'hit' if i == 0 else 'cold'}) "
              f"{dt:.4f} s{' (incl. compile)' if i < 2 else ''}")
    c = engine.counters
    if c["n_hit_batches"] < 1 or c["n_cold_batches"] < 1:
        raise AssertionError(f"hit/cold programs not both run: {dict(c)}")
    stats = engine.flush_stats()
    print(f"serve: {stats['n_batches']} batches of {SERVE_BATCH} "
          f"({c['n_hit_batches']} hit, {c['n_cold_batches']} cold), id hit rate "
          f"{stats['hit_rate_ids']:.4f}; logits vs dlrm.forward max |diff| "
          f"{worst!r}, bit-exact {exact}; peak_bytes_in_use {_peak_bytes()}")


def four_chip_phase(train_mod, cluster_at: int = 3, n_steps: int = 5):
    """The sharded supertable on four chips: the first step against the
    1-device step on the same initial state and batch, then steps through
    one ``cluster_sharded`` transition."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cce import CCE
    from repro.launch.mesh import make_host_mesh
    from repro.models import dlrm
    from repro.optim import sgd
    from repro.train.loop import make_train_step

    mesh = make_host_mesh(data=1, model=4)
    if set(mesh.devices.flat) != set(jax.devices()):
        raise AssertionError(f"mesh does not span the chips: {mesh.devices}")
    args = train_mod.parse_args([
        "--arch", "dlrm", "--no-reduced", "--batch", str(BATCH),
        "--model-shards", "4", "--cluster-every", str(cluster_at),
    ])
    trainer = _timed("build sharded trainer",
                     lambda: train_mod.build_dlrm_trainer(args))
    cfg = train_mod.dlrm_config(args)
    coll = cfg.collection
    placed = {d for leaf in jax.tree.leaves(trainer.state)
              for d in leaf.sharding.device_set}
    if placed != set(jax.devices()):
        raise AssertionError(f"state placed on {placed}, not all chips")
    cce_feats = [i for i, t in enumerate(coll.tables) if isinstance(t, CCE)]
    before = _ptr_epoch(coll, trainer.state, cce_feats[0])

    raw = next(trainer.data_iter)
    trainer.data_iter = itertools.chain([raw], trainer.data_iter)
    host_state = jax.device_get(trainer.state)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step1 = make_train_step(loss_fn, sgd(momentum=args.momentum),
                            lambda s: jnp.float32(args.lr),
                            trainer.static_buffers, donate=True)
    batch1 = {k: np.asarray(raw[k])[None] for k in ("dense", "label", "sparse")}
    _, m1 = step1(jax.device_put(host_state, jax.devices()[0]), batch1)
    loss1 = float(m1["loss"])

    def run(n):
        trainer.run(n)
        _block(trainer.state)

    _timed("first sharded step (incl. compile)", lambda: run(1))
    loss4 = float(trainer.history[0]["loss"])
    np.testing.assert_allclose(loss4, loss1, rtol=1e-5)
    print(f"sharded vs 1-device first step: loss {loss4!r} vs {loss1!r}")
    _timed(f"sharded steps 2-{cluster_at} incl. cluster_sharded transition",
           lambda: run(cluster_at - 1))
    _timed("first sharded step after the transition", lambda: run(1))
    run(n_steps - cluster_at - 1)
    losses = _losses_finite(trainer.history, "sharded train")
    _check_transition(coll, before, trainer, cce_feats)
    print(f"sharded train: {len(losses)} steps, loss {losses[0]:.5f} -> "
          f"{losses[-1]:.5f}; peak_bytes_in_use (chip 0) {_peak_bytes()}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded trainer and its "
                         "comparison with the 1-device step")
    args = ap.parse_args()
    device = check_device(4 if args.four_chips else 1)

    from repro.launch import train as train_mod
    from repro.launch.cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    if args.four_chips:
        four_chip_phase(train_mod)
    else:
        trainer, cfg = train_phase(train_mod)
        kernel_phase(trainer, cfg)
        serve_phase(trainer, cfg)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
