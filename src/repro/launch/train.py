"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 50 --emb cce --ckpt-dir /tmp/ckpt

``--reduced`` (the default) runs the CPU-sized family variant (what the
smoke tests use).  DLRM (the paper's model) is ``--arch dlrm``; with
``--no-reduced`` it trains ``configs.dlrm_criteo.CONFIG`` at the
published Criteo widths (26 Kaggle-vocabulary tables, emb_dim 16, MLPs
(512,256,64,16)/(512,256,1), CCE cap 8000) — the chip entry point:

    PYTHONPATH=src python -m repro.launch.train --arch dlrm --no-reduced \
        --steps 5 --batch 2048
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import clickstream_batches, lm_token_batches, ClickstreamConfig
from repro.launch.cache import use_compile_cache
from repro.models import dlrm, lm
from repro.obs import RunLog, TelemetryConfig
from repro.obs.runlog import default_manifest
from repro.optim import adagrad, adamw, sgd, cosine_schedule
from repro.optim.remap import remap_opt_state
from repro.stream import make_step_cell_counter
from repro.train.freq import IdFrequencyTracker
from repro.train.transition import transition_table
from repro.train.loop import (
    FailureInjector,
    StragglerMonitor,
    Trainer,
    init_state,
    make_train_step,
    split_buffers,
)


def _obs_kit(args, config_name: str):
    """(telemetry, trainer obs kwargs) for ``--obs PATH``: in-step health
    metrics + a structured run log; ``--profile-steps A B`` additionally
    opens a profiler window (DESIGN.md §10)."""
    telemetry, kw = None, {}
    # getattr throughout: tests drive the builders with hand-built
    # Namespaces that predate the obs flags
    obs = getattr(args, "obs", None)
    if obs:
        telemetry = TelemetryConfig()
        kw["runlog"] = RunLog(
            obs, manifest=default_manifest(
                config_name, mesh={"data": getattr(args, "data_shards", 1),
                                   "model": getattr(args, "model_shards", 1)},
            ),
        )
    profile_steps = getattr(args, "profile_steps", None)
    if profile_steps:
        kw["profile_steps"] = tuple(profile_steps)
        kw["profile_dir"] = getattr(args, "profile_dir", "profile")
    return telemetry, kw


def build_lm_trainer(cfg, args):
    key = jax.random.PRNGKey(args.seed)
    params, buffers = lm.init(key, cfg)
    dyn, static = split_buffers(buffers)
    optimizer = adamw(weight_decay=0.1)
    lr_fn = cosine_schedule(args.lr, args.warmup, args.steps)

    def loss_fn(p, b, mb):
        return lm.next_token_loss(p, b, cfg, mb, batch_axes=None)

    telemetry, obs_kw = _obs_kit(args, cfg.name)
    step = make_train_step(loss_fn, optimizer, lr_fn, static, accum=args.accum,
                           telemetry=telemetry)
    state = init_state(params, optimizer, dyn)
    data = lm_token_batches(
        cfg.vocab, args.batch, args.seq, seed=args.seed,
        n_codebooks=cfg.n_codebooks,
    )

    cluster_fn = None
    tracker = None
    if cfg.emb_method == "cce":
        emb = lm.make_emb(cfg)
        # token histogram feeds the transition's k-means sample; for
        # codebook models the ids are offset per codebook inside embed(),
        # so plain token counts don't map to table rows — fall back to
        # uniform sampling there (ROADMAP follow-on)
        if not cfg.n_codebooks:
            tracker = IdFrequencyTracker((emb.d1,), key="tokens")

        def cluster_fn(key, params, buffers, opt):
            ep, eb, update = transition_table(
                emb, key, params["emb"], buffers["emb"],
                counts=tracker.counts[0] if tracker is not None else None,
                chunk_size=1 << 18,  # LM vocabs can be huge: stream the pass
            )

            def upd(moments, _slot):
                return dict(moments, emb=update(moments["emb"]))

            return (dict(params, emb=ep), dict(buffers, emb=eb),
                    remap_opt_state(opt, upd))

    return Trainer(
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        cluster_fn=cluster_fn, cluster_every=args.cluster_every,
        id_tracker=tracker, accum=args.accum,
        failures=FailureInjector(tuple(args.fail_at)),
        monitor=StragglerMonitor(),
        seed=args.seed,
        **obs_kw,
    )


def build_dlrm_sharded_trainer(cfg, args, *, model: int, data_shards: int = 1):
    """The model-parallel DLRM trainer (ROADMAP item 1): supertable +
    optimizer moments codebook-sharded over the model mesh axis, ptr
    id-sharded the same way, host-translated rows pre-bucketed per shard,
    and the clustering transition running its O(d1) passes sharded over
    the same axis — no replica ever holds the full slab, full moments, or
    full pointer table (asserted by the ``dlrm_criteo_sharded`` audit's
    ``no-replicated-param`` rule at error severity)."""
    from repro.data.translate import HostTranslator, translate_batches
    from repro.launch.mesh import MODEL_AXIS, make_host_mesh
    from repro.launch.steps import build_dlrm_train_step

    mesh = make_host_mesh(data=data_shards, model=model)
    key = jax.random.PRNGKey(args.seed)
    params, buffers = dlrm.init(key, cfg)
    dyn, static = split_buffers(buffers)
    optimizer = sgd(momentum=args.momentum)

    def lr_fn(step):
        return jnp.float32(args.lr)

    # the sharded step carries no sketch counter: the tracker folds the
    # raw ids it reads off the batch on the host (Trainer.observe)
    tracker = dlrm_tracker(cfg, args)
    telemetry, obs_kw = _obs_kit(args, "dlrm_sharded")
    step, _, (state_shardings, _) = build_dlrm_train_step(
        cfg, mesh, batch_size=args.batch, accum=args.accum,
        optimizer=optimizer, lr_fn=lr_fn, static_buffers=static,
        with_sparse=tracker is not None,
        telemetry=telemetry,
    )
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        init_state(params, optimizer, dyn), state_shardings,
    )
    translator = HostTranslator(cfg.collection, buffers["emb"], n_shards=model)
    data = translate_batches(
        clickstream_batches(
            ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=args.seed),
            args.batch,
        ),
        translator,
    )

    def cluster_fn(key, params, buffers, opt):
        return dlrm.cluster_tables(
            key, params, buffers, cfg, opt, id_counts=tracker.counts,
            mesh=mesh, shard_axis=MODEL_AXIS,
        )

    return Trainer(
        step, state, static, data,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        cluster_fn=cluster_fn if tracker is not None else None,
        cluster_every=args.cluster_every, id_tracker=tracker,
        translator=translator, accum=args.accum,
        failures=FailureInjector(tuple(args.fail_at)),
        seed=args.seed,
        migrations=dlrm.checkpoint_migrations(cfg),
        state_shardings=state_shardings,
        **obs_kw,
    )


def dlrm_config(args):
    """The DLRM config the flags select: ``--reduced`` (default) the small
    synthetic config, ``--no-reduced`` the published Criteo widths.  The
    supertable codebook is padded to a multiple of ``--model-shards``."""
    from repro.configs import dlrm_criteo

    model = max(1, getattr(args, "model_shards", 1))
    cap = getattr(args, "emb_cap", None)
    if getattr(args, "reduced", True):
        return dlrm_criteo.reduced(
            emb_method=args.emb, cap=cap or 512, k_multiple=model,
        )
    return dataclasses.replace(
        dlrm_criteo.CONFIG, emb_method=args.emb,
        emb_param_cap=cap or dlrm_criteo.CONFIG.emb_param_cap,
        emb_k_multiple=model,
    )


def dlrm_tracker(cfg, args):
    """The id-frequency tracker feeding the transition's k-means (None
    without CCE).  At the published vocabularies the dense histograms
    would hold ~270 MB on the host (DESIGN.md §5), so full width uses
    the sketch tracker at vocabulary-independent memory."""
    from repro.configs import dlrm_criteo

    if args.emb != "cce":
        return None
    stream = None if getattr(args, "reduced", True) else dlrm_criteo.STREAM
    return dlrm.make_id_tracker(cfg, stream)


#: the optimizers the DLRM step trains with, by ``args.optimizer``: plain
#: SGD (the paper's default, ``--momentum``) and Adagrad (MLPerf
#: DLRM-DCNv2's; ``args.eps``, ``args.initial_accumulator``)
DLRM_OPTIMIZERS = {
    "sgd": lambda args: sgd(momentum=getattr(args, "momentum", 0.0)),
    "adagrad": lambda args: adagrad(eps=getattr(args, "eps", 1e-8),
                                    initial_accumulator=getattr(args, "initial_accumulator", 0.0)),
}


def dlrm_train_step(cfg, args, static_buffers, tracker, telemetry=None):
    """(donated jitted 1-device step, optimizer) — what the 1-device
    trainer runs; a sketch tracker's cell counting rides inside the step
    (no extra dispatch; the dense tracker contributes nothing).  The
    optimizer is ``DLRM_OPTIMIZERS[args.optimizer]`` (SGD when unnamed),
    the gradient clipped to ``args.clip_norm`` (1.0 when unnamed; None
    does not clip)."""
    name = getattr(args, "optimizer", "sgd")
    if name not in DLRM_OPTIMIZERS:
        raise ValueError(f"optimizer {name!r}: the DLRM step trains {sorted(DLRM_OPTIMIZERS)}")
    optimizer = DLRM_OPTIMIZERS[name](args)

    def lr_fn(step):
        return jnp.float32(args.lr)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, optimizer, lr_fn, static_buffers,
                           accum=args.accum, clip_norm=getattr(args, "clip_norm", 1.0),
                           telemetry=telemetry,
                           sketch_fn=make_step_cell_counter(tracker),
                           donate=True)
    return step, optimizer


def build_dlrm_trainer(args):
    cfg = dlrm_config(args)
    model = max(1, getattr(args, "model_shards", 1))
    if model > 1:
        return build_dlrm_sharded_trainer(
            cfg, args, model=model,
            data_shards=max(1, getattr(args, "data_shards", 1)),
        )
    params, buffers = dlrm.init(jax.random.PRNGKey(args.seed), cfg)
    dyn, static = split_buffers(buffers)
    tracker = dlrm_tracker(cfg, args)
    telemetry, obs_kw = _obs_kit(args, "dlrm")
    step, optimizer = dlrm_train_step(cfg, args, static, tracker, telemetry)
    state = init_state(params, optimizer, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=args.seed), args.batch
    )

    def cluster_fn(key, params, buffers, opt):
        return dlrm.cluster_tables(key, params, buffers, cfg, opt,
                                   id_counts=tracker.counts)

    return Trainer(
        step, state, static, data,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        cluster_fn=cluster_fn if tracker is not None else None,
        cluster_every=args.cluster_every, id_tracker=tracker,
        accum=args.accum,
        failures=FailureInjector(tuple(args.fail_at)),
        seed=args.seed,
        # pre-collection (per-feature emb layout) checkpoints restore too
        migrations=dlrm.checkpoint_migrations(cfg),
        **obs_kw,
    )


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--warmup", type=int, default=10)
    # model-parallel DLRM: shard the supertable over this many devices
    # (the mesh is (data_shards, model_shards); 1 = the plain 1-device path)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--data-shards", type=int, default=1)
    ap.add_argument("--emb", default="cce")
    # default: the config's own cap (512 reduced, 8000 at Criteo widths)
    ap.add_argument("--emb-cap", type=int, default=None)
    ap.add_argument("--cluster-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=0)
    # observability (DESIGN.md §10): --obs writes a structured run log
    # and turns on the in-step telemetry; --profile-steps A B dumps a
    # jax.profiler trace for that step window
    ap.add_argument("--obs", default=None, metavar="RUN.jsonl")
    ap.add_argument("--profile-steps", type=int, nargs=2, default=None)
    ap.add_argument("--profile-dir", default="profile")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    use_compile_cache()

    if args.arch == "dlrm":
        trainer = build_dlrm_trainer(args)
    elif not args.reduced:
        raise SystemExit("--no-reduced selects the published DLRM widths; "
                         "LM families train reduced only")
    else:
        cfg = configs.get_reduced(args.arch, emb_method=args.emb)
        trainer = build_lm_trainer(cfg, args)

    t0 = time.time()
    hist = trainer.run(args.steps)
    dt = time.time() - t0
    losses = [h["loss"] for h in hist]
    print(f"{args.arch}: {len(hist)} steps in {dt:.1f}s  "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"stragglers={len(trainer.monitor.flagged)}")
    if args.obs:
        trainer.runlog.close()
        print(f"run log: {args.obs}  "
              f"(summarize: python -m repro.obs summarize {args.obs})")


if __name__ == "__main__":
    main()
