"""The distributed training loop.

Pieces:
  * ``make_train_step`` — builds the jitted step: microbatch gradient
    accumulation (lax.scan), optional int8 gradient compression with error
    feedback, global-norm clipping, optimizer update.  Pure function of
    (state, batch) so it lowers/compiles for any mesh.
  * buffer split — embedding-table buffers mix arrays with static python
    ints.  Arrays ride the train state; ints are closed over statically.
    EVERYTHING the clustering transition rewrites (CCE ptr/hs/epoch) is
    therefore an array — a static leaf would leave the jitted step
    training against pre-transition hash functions.  Only buffers of the
    non-transitioning tables (embeddings.py hash coefficients) stay
    static.
  * ``Trainer`` — host-side orchestration: data feed, CCE clustering
    callback every ``cluster_every`` steps (the paper's Algorithm 3 line
    10 interleaving), async checkpointing, straggler monitor, failure
    injection for fault-tolerance tests, restart-exact resume.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, load_checkpoint
from repro.obs.pump import MetricsPump
from repro.obs.trace import ProfileWindow, span
from repro.optim import Optimizer, clip_by_global_norm
from repro.optim.compression import compressed_grad_transform, init_error_feedback

Pytree = Any


class TrainState(NamedTuple):
    params: Pytree
    opt: Pytree
    ebuf: Pytree  # dynamic (array) part of the embedding buffers
    step: jax.Array
    err: Pytree | None = None  # int8-compression error feedback


# --- buffer split -------------------------------------------------------------


def _is_arr(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray)) or hasattr(x, "shape")


def split_buffers(buffers: Pytree):
    """-> (dynamic, static).  ``dynamic`` has the same structure with None
    at static positions (a valid pytree arg); ``static`` is an opaque token
    to close over."""
    leaves, treedef = jax.tree.flatten(buffers)
    dynamic = jax.tree.unflatten(
        treedef, [leaf if _is_arr(leaf) else None for leaf in leaves]
    )
    static = (treedef, tuple((i, leaf) for i, leaf in enumerate(leaves) if not _is_arr(leaf)))
    return dynamic, static


def merge_buffers(dynamic: Pytree, static) -> Pytree:
    treedef, items = static
    n = treedef.num_leaves
    leaves: list = list(jax.tree.leaves(dynamic))
    # re-insert static leaves at their original flat positions
    out: list = []
    it = iter(leaves)
    static_at = dict(items)
    for i in range(n):
        out.append(static_at[i] if i in static_at else next(it))
    return jax.tree.unflatten(treedef, out)


# --- the step -----------------------------------------------------------------


def make_train_step(
    loss_fn: Callable[[Pytree, Pytree, Pytree], tuple[jax.Array, dict]],
    optimizer: Optimizer,
    lr_fn: Callable[[jax.Array], jax.Array],
    static_buffers,
    *,
    accum: int = 1,
    clip_norm: float | None = 1.0,
    compress_grads: bool = False,
    grad_specs: Pytree | None = None,
    sketch_fn: Callable[[Pytree], jax.Array] | None = None,
    telemetry=None,
    donate: bool = False,
):
    """loss_fn(params, buffers, microbatch) -> (loss, metrics dict).

    The returned step expects batch leaves shaped (accum, micro, ...).
    The averaged gradient is clipped to a global norm of ``clip_norm``
    (``None``: not clipped).
    ``grad_specs`` (optional PartitionSpec tree) shards the gradient
    accumulators over the data axis (ZeRO-2-style): each microbatch's
    cross-data reduction then lowers to a reduce-scatter instead of a full
    all-reduce — half the per-chip collective bytes on the dominant train
    collective (§Perf).

    ``sketch_fn(microbatch) -> (F, depth, width) int32`` (see
    ``stream.device.make_step_cell_counter``) embeds the frequency
    tracker's cell counter IN the step: the per-microbatch deltas
    accumulate across the gradient-accumulation scan and the summed delta
    rides out in ``metrics["sketch_delta"]`` — sketch tracking then adds
    ZERO extra device dispatches (the Trainer hands the delta to
    ``tracker.observe(batch, delta=...)``).

    ``telemetry`` (a ``repro.obs.TelemetryConfig``) rides the same
    protocol: in-step health metrics (per-emb-group grad/slab norms,
    per-leaf nonfinite counts, lookup occupancy / routing skew) computed
    from the averaged pre-clip grads and returned under
    ``metrics["telemetry"]`` — pure jnp reductions fused into the step's
    single program, so the launch count is unchanged (the
    ``train_step_telemetry`` audit spec asserts it).

    ``donate=True`` returns the step already jitted with
    ``donate_argnums=(0,)``: the TrainState's buffers (params, optimizer
    moments, embedding buffers, error feedback) are donated and the update
    happens in place — asserted via a lowering/donation check in
    tests/test_train_loop.py.
    """

    def _constrain_grads(g):
        if grad_specs is None:
            return g
        # map over the SPEC tree with is_leaf: PartitionSpec is tuple-like
        # and would otherwise be flattened as a sequence
        from jax.sharding import PartitionSpec as _P

        return jax.tree.map(
            lambda s, t: jax.lax.with_sharding_constraint(t, s),
            grad_specs, g, is_leaf=lambda x: isinstance(x, _P),
        )

    def train_step(state: TrainState, batch: Pytree):
        buffers = merge_buffers(state.ebuf, static_buffers)

        def micro(carry, mb):
            gsum, loss_sum = carry
            (loss, _m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, buffers, mb
            )
            gsum = jax.tree.map(lambda a, g: a + g.astype(a.dtype), gsum, grads)
            gsum = _constrain_grads(gsum)
            # the sketch cell delta is a scan OUTPUT (summed below), not
            # an extra dispatch: it lowers into the same program
            delta = sketch_fn(mb) if sketch_fn is not None else None
            return (gsum, loss_sum + loss), delta

        gzero = _constrain_grads(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        )
        if accum == 1:
            mb0 = jax.tree.map(lambda x: x[0], batch)
            (grads, loss_sum), delta = micro((gzero, jnp.float32(0)), mb0)
        else:
            (grads, loss_sum), deltas = jax.lax.scan(
                micro, (gzero, jnp.float32(0)), batch
            )
            delta = None if deltas is None else deltas.sum(axis=0)
        grads = jax.tree.map(lambda g: g / accum, grads)
        loss = loss_sum / accum

        health = None
        if telemetry is not None:
            from repro.obs.telemetry import telemetry_metrics

            # measured on the TRUE averaged gradient, before int8
            # compression and clipping rewrite it
            with jax.named_scope("telemetry"):
                health = telemetry_metrics(telemetry, grads, state.params, batch)

        err = state.err
        if compress_grads:
            grads, err = compressed_grad_transform(grads, err)

        if clip_norm is None:  # no clip: the norm is still reported
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        else:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        lr = lr_fn(state.step)
        new_params, new_opt = optimizer.update(grads, state.opt, state.params, lr)
        new_state = TrainState(
            params=new_params, opt=new_opt, ebuf=state.ebuf,
            step=state.step + 1, err=err,
        )
        metrics = {"loss": loss, "gnorm": gnorm, "lr": lr}
        if delta is not None:
            metrics["sketch_delta"] = delta
        if health is not None:
            metrics["telemetry"] = health
        return new_state, metrics

    if donate:
        return jax.jit(train_step, donate_argnums=(0,))
    return train_step


def init_state(params, optimizer: Optimizer, dynamic_buffers, *, compress_grads=False):
    return TrainState(
        params=params,
        opt=optimizer.init(params),
        ebuf=dynamic_buffers,
        step=jnp.zeros((), jnp.int32),
        err=init_error_feedback(params) if compress_grads else None,
    )


# --- host-side orchestration ----------------------------------------------------


class StragglerMonitor:
    """EMA step-time tracker; flags steps slower than mean + k·std.

    On a pod, per-host step times feed this via the metrics channel; the
    flagged host ids drive the re-shard/evict decision.  Here it watches
    the single-process step and is unit-tested with injected delays.

    SEMANTIC NOTE (the async-pump change): ``Trainer.run`` used to feed
    this dispatch+sync wall time (it forced ``block_until_ready`` every
    step).  It now feeds DISPATCH-TO-DISPATCH wall time: dispatch stays
    pipelined, and once the dispatch queue applies backpressure the
    interval converges to true per-step throughput — which is what a
    straggler threshold should watch.  Early-run intervals (queue still
    filling) are shorter than device step time; the ``warmup`` window
    absorbs them.  Thresholds tuned against the old synced numbers read
    slightly high against the new ones.
    """

    def __init__(self, alpha: float = 0.1, k: float = 4.0, warmup: int = 5):
        self.alpha, self.k, self.warmup = alpha, k, warmup
        self.mean = 0.0
        self.var = 0.0
        self.n = 0
        self.flagged: list[tuple[int, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        self.n += 1
        if self.n <= self.warmup:
            self.mean = dt if self.n == 1 else (self.mean + dt) / 2 if self.n == 2 else self.mean + self.alpha * (dt - self.mean)
            self.var = max(self.var, (dt - self.mean) ** 2)
            return False
        is_straggler = dt > self.mean + self.k * max(self.var, 1e-12) ** 0.5
        if is_straggler:
            self.flagged.append((step, dt))
        else:  # stragglers don't poison the EMA
            d = dt - self.mean
            self.mean += self.alpha * d
            self.var = (1 - self.alpha) * (self.var + self.alpha * d * d)
        return is_straggler


@dataclasses.dataclass
class FailureInjector:
    """Deterministic fault injection for restart tests: raises RuntimeError
    at the given steps (once each)."""

    at_steps: tuple[int, ...] = ()
    fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int):
        if step in self.at_steps and step not in self.fired:
            self.fired.add(step)
            raise RuntimeError(f"injected failure at step {step}")


def _cluster_fn_takes_opt(fn) -> bool:
    """The transition callback comes in two arities:
    ``(key, params, buffers)`` (legacy) and
    ``(key, params, buffers, opt) -> (params, buffers, opt)`` — the
    optimizer-state-aware form that remaps/resets per-row moments through
    the new cluster assignments (see ``repro.optim.remap``).

    Detection: an explicit ``fn.cluster_takes_opt`` attribute wins (set it
    on wrapped/partial callables where the signature lies); otherwise the
    4-arg form requires a parameter literally named ``opt``, or four
    REQUIRED positional parameters — a legacy callback with trailing
    optional extras (``def f(key, p, b, verbose=False)``) stays legacy."""
    explicit = getattr(fn, "cluster_takes_opt", None)
    if explicit is not None:
        return bool(explicit)
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    ps = [
        p for p in sig.parameters.values()
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    # opt is passed POSITIONALLY, so only positional kinds count — a
    # keyword-only `*, opt=None` stays on the legacy 3-arg call
    if any(p.name == "opt" for p in ps):
        return True
    return len([p for p in ps if p.default is p.empty]) >= 4


class Trainer:
    """data -> step -> [cluster] -> [checkpoint], restart-exact.

    ``cluster_fn`` is the CCE transition (Alg. 3); it runs OUTSIDE the
    jitted step every ``cluster_every`` steps, like the paper's per-epoch
    clustering.  The 4-arg form additionally receives (and returns) the
    optimizer state so per-row moments survive the transition; both the
    params and the remapped optimizer state land back in ``TrainState``,
    which is what the checkpoint saves — resume after a transition is
    exact."""

    def __init__(
        self,
        train_step,
        state: TrainState,
        static_buffers,
        data_iter,
        *,
        ckpt_dir: str | None = None,
        ckpt_every: int = 0,
        keep_last: int = 3,
        cluster_fn=None,
        cluster_every: int = 0,
        cluster_max: int = 0,
        id_tracker=None,
        trigger=None,
        translator=None,
        accum: int = 1,
        monitor: StragglerMonitor | None = None,
        failures: FailureInjector | None = None,
        seed: int = 0,
        migrations=(),
        state_shardings=None,
        runlog=None,
        pump: MetricsPump | None = None,
        pump_lag: int = 8,
        history_max: int | None = 10_000,
        sync_every: int = 0,
        profile_steps: tuple[int, int] | None = None,
        profile_dir: str | None = None,
    ):
        self.train_step = train_step
        self.state = state
        self.static_buffers = static_buffers
        self.data_iter = data_iter
        self.ckpt = CheckpointManager(ckpt_dir, keep_last) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.cluster_fn = cluster_fn
        self._cluster_takes_opt = (
            cluster_fn is not None and _cluster_fn_takes_opt(cluster_fn)
        )
        self.cluster_every = cluster_every
        self.cluster_max = cluster_max
        self.id_tracker = id_tracker  # feeds the transition's k-means sample
        # adaptive schedule: a repro.stream.ClusterTrigger evaluated on
        # every closed tracker window — fires the SAME transition the
        # periodic schedule does (both can be active; cluster_max caps
        # their union).  Requires a windowed tracker (poll_window).
        self.trigger = trigger
        if trigger is not None:
            windowed = getattr(id_tracker, "poll_window", None) is not None
            window = getattr(getattr(id_tracker, "config", None), "window", None)
            if not windowed or window == 0:
                warnings.warn(
                    "Trainer(trigger=...) needs a windowed tracker "
                    "(SketchFrequencyTracker with StreamConfig(window>0)); "
                    "the adaptive schedule will never evaluate"
                )
        # host-translating pipelines (data.translate.HostTranslator
        # wrapped around data_iter) mirror the pointer buffers — the
        # mirrors go stale the moment a transition rewrites ptr/hs, so
        # the Trainer re-syncs the translator after every transition and
        # after a checkpoint restore (translate_batches is lazy: the
        # next batch already uses the fresh mirrors)
        self.translator = translator
        self.clusters_done = 0
        self.accum = accum
        self.monitor = monitor or StragglerMonitor()
        self.failures = failures
        self.seed = seed
        # (to_old, to_new) template/convert pairs for checkpoints written
        # under older state layouts (e.g. dlrm.checkpoint_migrations for
        # pre-collection per-feature emb trees).  Trackers contribute
        # their own (the sketch tracker restores legacy DENSE id_counts
        # by ingesting the histograms — exact on the head ids).
        tracker_migrations = getattr(id_tracker, "checkpoint_migrations", None)
        self.migrations = tuple(migrations) + (
            tuple(tracker_migrations()) if tracker_migrations else ()
        )
        # a TrainState-shaped tree of jax.sharding.Sharding for the
        # sharded trainer (launch.steps.dlrm_state_shardings): state
        # produced OUTSIDE the donated jitted step — the eager clustering
        # transition, a checkpoint restore — is device_put back onto the
        # step's layout before the next step runs, so donation never has
        # to reshard and no replica silently ends up with the full slab
        self.state_shardings = state_shardings
        # observability (DESIGN.md §10): metrics leave the device through
        # the async pump — a ring drained ``pump_lag`` steps behind the
        # dispatch front, so reading a metric never syncs the pipeline.
        # ``history`` is the pump's bounded record deque (``history_max``
        # caps a long run's host memory); it is EXACT after run() returns
        # (final flush) and, mid-run, after every ``sync_every`` steps
        # when that is set — tests that read history mid-run set
        # sync_every=1 and see the old always-synced behavior.
        self.runlog = runlog
        self.pump = pump or MetricsPump(
            lag=pump_lag, maxlen=history_max,
            sink=runlog.log_step if runlog is not None else None,
        )
        self.sync_every = sync_every
        self.profile = (
            ProfileWindow(*profile_steps, log_dir=profile_dir or "profile")
            if profile_steps is not None else None
        )
        self._last_dispatch: float | None = None

    @property
    def history(self):
        return self.pump.history

    def _place(self, state: TrainState) -> TrainState:
        if self.state_shardings is None:
            return state
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s), state, self.state_shardings
        )

    def _reshape_accum(self, batch):
        def r(x):
            x = np.asarray(x)
            if self.accum == 1:
                return x[None]
            return x.reshape(self.accum, x.shape[0] // self.accum, *x.shape[1:])
        return {k: r(v) for k, v in batch.items() if k != "step"}

    def run(self, n_steps: int):
        # ONE sync to seed the host step mirror (blocking on state.step
        # every iteration — like the old loop did — waits for the whole
        # previous step and kills async dispatch; the mirror is exact
        # because the step increments by 1 and transitions/restores only
        # happen between run() calls or below, where we track them)
        step = int(self.state.step)
        try:
            for _ in range(n_steps):
                if self.profile is not None:
                    self.profile.observe(step)
                if self.failures is not None:
                    try:
                        self.failures.maybe_fail(step)
                    except Exception as e:
                        # make the records of the completed steps durable
                        # before the crash propagates, and log the fire
                        # (dedupe off: a from-scratch restart re-fires at
                        # the same step and both fires are real)
                        self.pump.flush()
                        if self.runlog is not None:
                            self.runlog.append(
                                "fault", step=step, dedupe=False, error=str(e),
                            )
                        raise
                with span("next-batch"):
                    raw = next(self.data_iter)
                    batch = self._reshape_accum(raw)
                with span("dispatch"):
                    self.state, metrics = self.train_step(self.state, batch)
                # dispatch-to-dispatch wall time (see StragglerMonitor's
                # semantic note): attributed to this step, first step of a
                # run() has no previous dispatch to measure from
                t1 = time.perf_counter()
                dt = None
                if self._last_dispatch is not None:
                    dt = t1 - self._last_dispatch
                    self.monitor.observe(step, dt)
                self._last_dispatch = t1
                # a step built with sketch_fn= already computed the tracker's
                # cell delta inside its single launch — hand it over so the
                # tracker skips its own counter dispatch (zero extra
                # dispatches; the host head/ring bookkeeping is unchanged)
                delta = metrics.pop("sketch_delta", None)
                if self.id_tracker is not None:
                    with span("sketch-fold"):
                        if delta is not None:
                            self.id_tracker.observe(raw, delta=delta)
                        else:
                            self.id_tracker.observe(raw)
                self.pump.push(step, metrics, extra={"dt": dt})

                new_step = step + 1
                # adaptive schedule: a windowed tracker snapshots statistics
                # at window close; the trigger turns them into a fire/hold
                # decision.  Deterministic given the batch stream + restored
                # trigger state, so resume replays the schedule exactly.
                can_cluster = self.cluster_fn is not None and (
                    not self.cluster_max or self.clusters_done < self.cluster_max
                )
                triggered = False
                if self.id_tracker is not None and self.trigger is not None:
                    poll = getattr(self.id_tracker, "poll_window", None)
                    stats = poll() if poll is not None else None
                    if stats is not None:
                        # the availability gate rides INTO the trigger: a fire
                        # that cannot run a transition must not commit
                        # fire-state (reference reset, spacing counter)
                        ev = self.trigger.update(
                            stats, step=new_step, can_fire=can_cluster
                        )
                        triggered = ev.fire
                        if self.runlog is not None:
                            # replayed evaluations after a resume dedupe on
                            # (event, step) — same policy restore_latest
                            # applies to trigger.events
                            self.runlog.append("trigger", **ev.as_dict())
                periodic = bool(
                    self.cluster_every and new_step % self.cluster_every == 0
                )
                if can_cluster and (periodic or triggered):
                    # the one phase that traces device work under its name
                    with jax.named_scope("transition"), span("transition"):
                        if self.id_tracker is not None:  # async folds must land
                            getattr(self.id_tracker, "flush", lambda: None)()
                        key = jax.random.fold_in(
                            jax.random.PRNGKey(self.seed), new_step
                        )
                        buffers = merge_buffers(self.state.ebuf, self.static_buffers)
                        if self._cluster_takes_opt:
                            params, buffers, opt = self.cluster_fn(
                                key, self.state.params, buffers, self.state.opt
                            )
                        else:
                            params, buffers = self.cluster_fn(
                                key, self.state.params, buffers
                            )
                            opt = self.state.opt
                        dyn, self.static_buffers = split_buffers(buffers)
                        # int8-EF residuals are per-row state like the moments:
                        # the rewritten rows make them meaningless, and (unlike
                        # moments) zeroing them is always sound — EF only
                        # corrects future quantization, it carries no required
                        # state
                        err = (
                            init_error_feedback(params)
                            if self.state.err is not None else None
                        )
                        self.state = self._place(self.state._replace(
                            params=params, ebuf=dyn, opt=opt, err=err
                        ))
                        self.clusters_done += 1
                        if self.translator is not None:  # mirrors went stale
                            self.translator.update(buffers["emb"])
                    if self.runlog is not None:
                        self.runlog.append(
                            "transition", step=new_step,
                            reason="trigger" if triggered else "periodic",
                            clusters_done=self.clusters_done,
                        )

                if self.ckpt and self.ckpt_every and new_step % self.ckpt_every == 0:
                    # flush first: every step record at or before the
                    # checkpointed step is durable before the save event —
                    # resume-time replays then dedupe against a complete
                    # prefix of the log
                    self.pump.flush()
                    with span("checkpoint"):
                        self.ckpt.save_async(new_step, self._ckpt_tree())
                    if self.runlog is not None:
                        self.runlog.append("checkpoint_save", step=new_step)
                elif self.sync_every and new_step % self.sync_every == 0:
                    self.pump.flush()
                step = new_step
        finally:
            self.pump.flush()
            if self.profile is not None:
                self.profile.close()
        if self.ckpt:
            self.ckpt.wait()
        return list(self.history)

    def _ckpt_tree(self):
        # clusters_done and the id histograms ride the checkpoint so a
        # restart cannot re-run (or skip) transitions against cluster_max,
        # and the k-means sampling distribution resumes exactly — the
        # transition schedule is part of the training state, not of the
        # host process.
        tree = {"state": self.state, "clusters_done": np.int32(self.clusters_done)}
        if self.id_tracker is not None:
            tree["id_counts"] = self.id_tracker.state_tree()
        if self.trigger is not None:
            # trigger state is training state too: resuming without it
            # would re-arm the entropy reference and replay fires
            tree["trigger"] = self.trigger.state_tree()
        return tree

    def _stored_n_leaves(self):
        """Leaf count of the latest committed checkpoint (None if none) —
        sizes the id_counts wildcard placeholders."""
        from repro.checkpoint.store import list_checkpoints
        import json
        import os

        ckpts = list_checkpoints(self.ckpt.directory)
        if not ckpts:
            return None
        with open(os.path.join(ckpts[-1][1], "manifest.json")) as f:
            return int(json.load(f)["n_leaves"])

    def _with_id_counts_placeholder(self, template):
        """When the WRITER had a tracker this Trainer doesn't, absorb the
        saved id_counts leaves via zero-size wildcard placeholders sized
        against THIS template's leaf count (the histograms are dropped).
        Must be applied per candidate layout — legacy layouts have
        different leaf counts, so one global placeholder cannot fit all."""
        if self.id_tracker is not None or "id_counts" in template:
            return None
        n_stored = self._stored_n_leaves()
        if n_stored is None:
            return None
        extra = n_stored - len(jax.tree.leaves(template))
        if extra <= 0:
            return None
        return dict(template, id_counts=[np.zeros(0)] * extra)

    def _restore_templates(self):
        """Candidate checkpoint layouts, most- to least-informative: the
        current config's layout, then the layouts a differently-configured
        writer could have produced (tracker-less: no id_counts; pre-
        transition-subsystem: state only)."""
        # template forms, not live state (no _ckpt_tree: that would copy
        # and flush the full live tracker only to be overwritten here):
        # a sectioned checkpoint MISSING one of these sections restores
        # the template value, so templates must be deterministic fresh
        # state (and the trigger's prev-head leaves become zero-size
        # wildcards — the stored row count depends on whether the WRITER
        # had closed a window yet)
        cur = {"state": self.state, "clusters_done": np.int32(self.clusters_done)}
        if self.id_tracker is not None:
            tmpl = getattr(self.id_tracker, "state_template", None)
            cur["id_counts"] = tmpl() if tmpl else self.id_tracker.state_tree()
        if self.trigger is not None:
            cur["trigger"] = self.trigger.state_template()
        templates = [cur]
        if self.trigger is not None:
            # writer predates the trigger (sectioned checkpoints align
            # this by name; the variant covers pre-section writers)
            templates.append(
                {k: v for k, v in cur.items() if k != "trigger"}
            )
        base = {"state": self.state, "clusters_done": np.int32(0)}
        if self.id_tracker is not None:
            templates.append(base)  # writer had no tracker
        else:
            with_counts = self._with_id_counts_placeholder(base)
            if with_counts is not None:  # writer-side id_counts, dropped
                templates.append(with_counts)
        templates.append({"state": self.state})  # pre-transition layout
        return templates

    def restore_latest(self):
        self.ckpt.wait()  # an async save may still be in flight post-crash
        templates = self._restore_templates()
        candidates = [(t, None) for t in templates]
        # legacy layouts: derive each old-layout template from the current
        # one and restore through its converter (checkpoint.load_checkpoint
        # picks the first candidate whose leaves match).  The id_counts
        # placeholder is re-sized against each CONVERTED template — legacy
        # layouts have different leaf counts.  Migrations also COMPOSE
        # pairwise: a checkpoint can be old along two independent axes at
        # once (pre-collection emb layout AND dense id_counts) — each
        # to_old chains on the other's template, converts apply in
        # reverse, so the combined-legacy layout restores too.
        pairs = list(self.migrations)
        for a_old, a_new in self.migrations:
            for b_old, b_new in self.migrations:
                if b_old is a_old:
                    continue

                def chained_old(t, ao=a_old, bo=b_old):
                    return bo(ao(t))

                def chained_new(tree, an=a_new, bn=b_new):
                    tree = bn(tree) if bn is not None else tree
                    return an(tree) if an is not None else tree

                pairs.append((chained_old, chained_new))
        for to_old, to_new in pairs:
            for t in templates:
                try:
                    old_t = to_old(t)
                except (KeyError, IndexError, TypeError, ValueError):
                    # two migrations along the SAME axis (e.g. two emb
                    # layout converters) don't compose — the structural
                    # mismatch is expected and the chain is simply not a
                    # candidate layout.  Anything else (AttributeError
                    # from a buggy migration, MemoryError, ...) is a real
                    # defect and propagates.
                    continue
                candidates.append((old_t, to_new))
                with_counts = self._with_id_counts_placeholder(old_t)
                if with_counts is not None:
                    candidates.append((with_counts, to_new))
        step, tree, _ = load_checkpoint(self.ckpt.directory, migrations=candidates)
        self.state = self._place(tree["state"])
        self.clusters_done = int(tree.get("clusters_done", 0))
        if self.id_tracker is not None:
            if "id_counts" in tree:
                self.id_tracker.load_state_tree(tree["id_counts"])
            else:
                # the matched layout had no usable histogram section (old
                # writer, or a StreamConfig change made the shapes
                # unmatchable): restore the deterministic fresh state the
                # sectioned path would have installed, and surface it —
                # leaving the live tracker's POST-checkpoint observations
                # in place would silently diverge in in-process recovery
                template = getattr(self.id_tracker, "state_template", None)
                if template is not None:
                    self.id_tracker.load_state_tree(template())
                warnings.warn(
                    "checkpoint had no usable id_counts section; tracker "
                    "restarted fresh from the restored step"
                )
        if self.trigger is not None:
            if "trigger" in tree:
                self.trigger.load_state_tree(tree["trigger"])
                # windows evaluated between this checkpoint and the crash
                # will be re-evaluated on replay — drop their events so
                # the log shows each closed window once
                self.trigger.events = [
                    e for e in self.trigger.events if e.step <= step
                ]
            else:
                # same deterministic semantics as the sectioned path
                # (missing section restores the fresh template)
                self.trigger.load_state_tree(self.trigger.state_template())
                self.trigger.events = [
                    e for e in self.trigger.events if e.step <= step
                ]
                warnings.warn(
                    "checkpoint had no trigger section; trigger restarted "
                    "fresh from the restored step"
                )
        if self.translator is not None:  # mirrors must match restored ptr/hs
            self.translator.update(
                merge_buffers(self.state.ebuf, self.static_buffers)["emb"]
            )
        # the restore gap is not a step interval; don't let it poison the
        # monitor's dispatch-to-dispatch EMA
        self._last_dispatch = None
        if self.runlog is not None:
            self.runlog.append("checkpoint_restore", step=step, dedupe=False)
        return step
