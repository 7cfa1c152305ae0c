"""Training loop: accumulation equivalence, fault tolerance, restart-exact
resume, straggler monitor, CCE clustering callback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dlrm_criteo
from repro.data import ClickstreamConfig, clickstream_batches
from repro.models import dlrm
from repro.optim import sgd
from repro.train.loop import (
    FailureInjector,
    StragglerMonitor,
    Trainer,
    init_state,
    make_train_step,
    merge_buffers,
    split_buffers,
)


def _setup(emb="cce", accum=1, seed=0):
    cfg = dlrm_criteo.reduced(emb_method=emb, cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(seed), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static,
                           accum=accum)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=seed), 32 * accum
    )
    return cfg, step, state, static, data


def test_loss_decreases():
    cfg, step, state, static, data = _setup()
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data)
    hist = tr.run(40)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.01, (first, last)


def test_split_merge_roundtrip():
    cfg = dlrm_criteo.reduced()
    _, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    dyn, static = split_buffers(buffers)
    back = merge_buffers(dyn, static)
    assert jax.tree.structure(back) == jax.tree.structure(buffers)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(buffers)):
        if hasattr(a, "shape"):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert a == b


def test_grad_accum_equivalence():
    """accum=2 over the same 64 samples == accum=1 (up to float assoc)."""
    _, step1, state1, static, _ = _setup(accum=1)
    _, step2, state2, _, _ = _setup(accum=2)
    data = next(clickstream_batches(
        ClickstreamConfig(seed=3,
                          vocab_sizes=dlrm_criteo.reduced().vocab_sizes), 64))
    b1 = {k: np.asarray(v)[None] for k, v in data.items() if k != "step"}
    b2 = {k: np.asarray(v).reshape(2, 32, *np.asarray(v).shape[1:])
          for k, v in data.items() if k != "step"}
    s1, m1 = step1(state1, b1)
    s2, m2 = step2(state2, b2)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_checkpoint_restart_exact(tmp_path):
    """Kill at step 7 (injected), restore, replay — final params bitwise
    equal to an uninterrupted run (deterministic data by (seed, step))."""
    def run(fail: bool):
        cfg, step, state, static, data = _setup(seed=1)
        tr = Trainer(
            jax.jit(step, donate_argnums=(0,)), state, static, data,
            ckpt_dir=str(tmp_path / ("a" if fail else "b")),
            ckpt_every=5,
            failures=FailureInjector((7,)) if fail else None,
        )
        if fail:
            with pytest.raises(RuntimeError):
                tr.run(12)
            # restart: restore + rebuild the data stream from the step
            restored = tr.restore_latest()
            assert restored == 5
            cfg2, step2, _, static2, _ = _setup(seed=1)
            data2 = clickstream_batches(
                ClickstreamConfig(vocab_sizes=cfg2.vocab_sizes, seed=1),
                32, start_step=restored,
            )
            tr2 = Trainer(jax.jit(step2, donate_argnums=(0,)), tr.state,
                          static2, data2, ckpt_dir=str(tmp_path / "a"))
            tr2.run(12 - restored)
            return tr2.state
        tr.run(12)
        return tr.state

    s_fail = run(True)
    s_clean = run(False)
    for a, b in zip(jax.tree.leaves(s_fail.params), jax.tree.leaves(s_clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cluster_callback_runs_and_training_continues():
    cfg, step, state, static, data = _setup(emb="cce")

    def cluster_fn(key, params, buffers):
        return dlrm.cluster_tables(key, params, buffers, cfg)

    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 cluster_fn=cluster_fn, cluster_every=10, cluster_max=2)
    hist = tr.run(25)
    assert tr.clusters_done == 2
    assert np.isfinite(hist[-1]["loss"])
    # training still improves after clustering
    assert np.mean([h["loss"] for h in hist[-5:]]) < np.mean(
        [h["loss"] for h in hist[:5]]) + 0.05


def test_train_state_donated_no_copy():
    """The jitted step donates the whole TrainState (params, moments,
    embedding buffers, step counter): every state leaf must carry an
    input-output alias in the lowered program — the in-place-update
    contract behind the single-launch hot path."""
    cfg, _, state, static, data = _setup()
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(
        loss_fn, opt, lambda s: jnp.float32(0.05), static, donate=True
    )
    batch = {
        k: np.asarray(v)[None] for k, v in next(data).items() if k != "step"
    }
    # every donated state buffer aliases an output in the lowering; the
    # DonationCoverage audit rule owns the aliasing-count check
    from repro.analysis import AuditProgram, DonationCoverage

    prog = AuditProgram.capture(
        step, state, batch, name="train_step", donate_argnums=(0,)
    )
    assert DonationCoverage().check(prog) == []
    # and the donated step still runs + matches the undonated math (up to
    # compilation-level float reassociation — donation changes the
    # program XLA sees, not the math)
    ref_step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    s_ref, m_ref = ref_step(state, batch)
    s_don, m_don = step(state, batch)
    np.testing.assert_allclose(float(m_don["loss"]), float(m_ref["loss"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s_don.params), jax.tree.leaves(s_ref.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-7
        )


def _setup_sketch(in_step: bool, seed=0, accum=1, window=4):
    from repro.configs import dlrm_criteo as dc
    from repro.stream import make_step_cell_counter

    cfg = dc.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(seed), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    tracker = dlrm.make_id_tracker(
        cfg, dc.reduced_stream(window=window, async_fold=True)
    )
    sketch_fn = make_step_cell_counter(tracker) if in_step else None
    step = make_train_step(
        loss_fn, opt, lambda s: jnp.float32(0.05), static,
        accum=accum, sketch_fn=sketch_fn, donate=True,
    )
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=seed), 32 * accum
    )
    return cfg, step, state, static, data, tracker


def test_in_step_sketch_delta_matches_dispatch_path():
    """The cell delta produced INSIDE the donated train step must leave
    the tracker in bit-identical state to the PR-4 standalone-dispatch
    path — and the tracker's own counter must never be dispatched."""
    _, step_a, state_a, static_a, data_a, tk_a = _setup_sketch(False)
    tr_a = Trainer(step_a, state_a, static_a, data_a, id_tracker=tk_a)
    tr_a.run(9)
    tk_a.flush()

    _, step_b, state_b, static_b, data_b, tk_b = _setup_sketch(True)

    def boom(*a, **k):
        raise AssertionError("tracker dispatched its own cell counter")

    tk_b._cell_counter = boom  # the in-step delta must make this dead code
    tr_b = Trainer(step_b, state_b, static_b, data_b, id_tracker=tk_b)
    tr_b.run(9)
    tk_b.flush()

    for a, b in zip(tk_a.state_tree(), tk_b.state_tree()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the training math is untouched by carrying the delta
    for a, b in zip(
        jax.tree.leaves(tr_a.state.params), jax.tree.leaves(tr_b.state.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_in_step_sketch_delta_accumulates_over_microbatches():
    """accum > 1: the per-microbatch deltas sum across the scan, so the
    tracker sees the WHOLE batch exactly once (window=0: no decay, so
    the folded mass is exactly the id count)."""
    _, step1, state1, static1, data1, tk1 = _setup_sketch(True, accum=1, window=0)
    _, step2, state2, static2, data2, tk2 = _setup_sketch(True, accum=2, window=0)
    tr1 = Trainer(step1, state1, static1, data1, id_tracker=tk1, accum=1)
    tr2 = Trainer(step2, state2, static2, data2, id_tracker=tk2, accum=2)
    tr1.run(4)
    tr2.run(4)
    tk1.flush()
    tk2.flush()
    # accum=2 consumed 64-id batches vs accum=1's 32-id batches: compare
    # total folded mass instead of bitwise state (different streams)
    m1 = sum(tk1.features[f].mass for f in tk1.tracked)
    m2 = sum(tk2.features[f].mass for f in tk2.tracked)
    assert m1 == 4 * 32 * len(tk1.tracked)
    assert m2 == 4 * 64 * len(tk2.tracked)


def test_in_step_sketch_restart_exact(tmp_path):
    """Checkpoint resume with the in-step delta path: kill at step 7,
    restore, replay — params AND tracker state bitwise equal to the
    uninterrupted run."""

    def run(fail: bool):
        cfg, step, state, static, data, tracker = _setup_sketch(True, seed=2)
        tr = Trainer(
            step, state, static, data,
            ckpt_dir=str(tmp_path / ("a" if fail else "b")), ckpt_every=5,
            id_tracker=tracker,
            failures=FailureInjector((7,)) if fail else None,
        )
        if fail:
            with pytest.raises(RuntimeError):
                tr.run(12)
            restored = tr.restore_latest()
            assert restored == 5
            _, step2, _, static2, _, tracker2 = _setup_sketch(True, seed=2)
            tracker2.load_state_tree(tracker.state_tree())
            data2 = clickstream_batches(
                ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=2),
                32, start_step=restored,
            )
            tr2 = Trainer(
                step2, tr.state, static2, data2,
                ckpt_dir=str(tmp_path / "a"), id_tracker=tracker2,
            )
            tr2.run(12 - restored)
            return tr2.state, tracker2
        tr.run(12)
        return tr.state, tracker

    (s_fail, tk_fail), (s_clean, tk_clean) = run(True), run(False)
    for a, b in zip(jax.tree.leaves(s_fail.params), jax.tree.leaves(s_clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tk_fail.flush()
    tk_clean.flush()
    for a, b in zip(tk_fail.state_tree(), tk_clean.state_tree()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(warmup=3, k=3.0)
    for i in range(20):
        mon.observe(i, 0.10 + 0.001 * (i % 3))
    assert not mon.flagged
    assert mon.observe(20, 1.0)  # 10x outlier
    assert mon.flagged[-1][0] == 20
    # EMA not poisoned: next normal step is not flagged
    assert not mon.observe(21, 0.101)


def test_failure_injector_fires_once():
    fi = FailureInjector((3,))
    fi.maybe_fail(2)
    with pytest.raises(RuntimeError):
        fi.maybe_fail(3)
    fi.maybe_fail(3)  # second pass: already fired


@pytest.mark.parametrize("clip_norm", [0.05, None])
def test_clip_norm_reaches_the_step(clip_norm):
    """A clip other than 1.0 (and None, no clip) reaches the update: one
    SGD step moves the params by lr times the clipped (or raw) gradient."""
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    dyn, static = split_buffers(buffers)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, sgd(), lambda s: jnp.float32(1.0), static,
                           clip_norm=clip_norm)
    data = next(clickstream_batches(ClickstreamConfig(vocab_sizes=cfg.vocab_sizes), 32))
    batch = {k: np.asarray(v)[None] for k, v in data.items() if k != "step"}
    new, m = jax.jit(step)(init_state(params, sgd(), dyn), batch)
    moved = np.sqrt(sum(float(jnp.sum((a - b) ** 2)) for a, b in
                        zip(jax.tree.leaves(params), jax.tree.leaves(new.params))))
    assert float(m["gnorm"]) > 0.05  # the clip bites
    want = float(m["gnorm"]) if clip_norm is None else clip_norm
    np.testing.assert_allclose(moved, want, rtol=1e-4)


def test_entry_point_picks_the_optimizer_by_name():
    """``dlrm_train_step`` builds ``DLRM_OPTIMIZERS[args.optimizer]`` and
    hands ``args.clip_norm`` to the step; an unknown name is refused."""
    import argparse

    from repro.launch import train

    assert set(train.DLRM_OPTIMIZERS) == {"sgd", "adagrad"}
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    dyn, static = split_buffers(buffers)
    args = argparse.Namespace(accum=1, optimizer="adagrad", lr=0.01, eps=1e-8,
                              initial_accumulator=0.0, clip_norm=None)
    step, opt = train.dlrm_train_step(cfg, args, static, None)
    w0 = np.asarray(params["top"][0]["w"])  # the step donates the state
    state = init_state(params, opt, dyn)
    assert set(state.opt) == {"a"}
    data = next(clickstream_batches(ClickstreamConfig(vocab_sizes=cfg.vocab_sizes), 32))
    new, _ = step(state, {k: np.asarray(v)[None] for k, v in data.items() if k != "step"})
    # Adagrad from a zero accumulator moves every element with a gradient by lr
    moved = np.abs(np.asarray(new.params["top"][0]["w"]) - w0)
    np.testing.assert_allclose(moved.max(), 0.01, rtol=1e-3)
    with pytest.raises(ValueError, match="adagrad"):
        train.dlrm_train_step(cfg, argparse.Namespace(accum=1, optimizer="lamb", lr=0.1),
                              static, None)
