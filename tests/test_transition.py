"""The clustering-transition subsystem (Alg. 3 as rebuilt in this repo):

* optimizer-moment remap/reset across ``cluster()`` — no stale-moment
  leakage through the 4-arg Trainer protocol,
* single-pass full-vocab assignment — chunked bit-matches unchunked, and
  exactly ONE full-vocab materialization per transition,
* the Pallas ``kmeans_assign`` kernel route (interpret mode on CPU)
  matches the jnp path,
* the shard_map'd distributed transition reproduces the serial one on a
  1-device axis, and runs both phases (weighted k-means + full-vocab
  assignment) sharded on a forced 4-device host,
* count-WEIGHTED k-means: a weighted Lloyd step equals the unweighted
  step on the expanded multiset, and the transition feeds unique observed
  ids + counts instead of a with-replacement sample,
* restart-exact resume across a transition (params AND remapped moments).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dlrm_criteo
from repro.core.cce import CCE
from repro.data import ClickstreamConfig, clickstream_batches
from repro.launch.mesh import make_mesh
from repro.models import dlrm
from repro.optim import sgd
from repro.optim.remap import remap_opt_state
from repro.train.loop import (
    FailureInjector,
    Trainer,
    init_state,
    make_train_step,
    split_buffers,
)


@pytest.fixture(scope="module")
def cce_state():
    # d1 > 256*k so the k-means sample is a strict subset of the vocab and
    # the full-vocab pass is distinguishable from the sample pass
    cce = CCE(d1=3000, d2=16, k=8, c=4, seed_salt=3)
    params, buffers = cce.init(jax.random.PRNGKey(0))
    return cce, params, buffers


# --- single-pass, chunked, kernel-backed assignment --------------------------


def test_chunked_assignment_bitmatches_unchunked(cce_state):
    cce, params, buffers = cce_state
    cents = jax.random.normal(jax.random.PRNGKey(1), (cce.c, cce.k, cce.dsub))
    a_full = cce.assign_all(params, buffers, cents, use_kernel=False)
    a_chunk = cce.assign_all(params, buffers, cents, chunk_size=97, use_kernel=False)
    assert a_full.shape == (cce.c, cce.d1)
    np.testing.assert_array_equal(np.asarray(a_full), np.asarray(a_chunk))


def test_cluster_is_single_full_vocab_pass(monkeypatch, cce_state):
    cce, params, buffers = cce_state
    calls = []
    orig = CCE.materialize

    def spy(self, p, b, ids):
        calls.append(int(ids.shape[0]))
        return orig(self, p, b, ids)

    monkeypatch.setattr(CCE, "materialize", spy)
    cce.cluster(jax.random.PRNGKey(3), params, buffers)
    assert sum(1 for n in calls if n == cce.d1) == 1, calls
    # chunked: the vocab is streamed, (c, d1, dsub) never materializes
    calls.clear()
    cce.cluster(jax.random.PRNGKey(3), params, buffers, chunk_size=500)
    assert max(calls) < cce.d1 and sum(n for n in calls if n <= 500) == cce.d1


def test_cluster_kernel_path_matches_jnp(cce_state):
    cce, params, buffers = cce_state
    p_j, b_j = cce.cluster(jax.random.PRNGKey(2), params, buffers, use_kernel=False)
    p_k, b_k = cce.cluster(jax.random.PRNGKey(2), params, buffers, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(p_j["tables"]), np.asarray(p_k["tables"]), rtol=1e-6
    )
    agree = (np.asarray(b_j["ptr"]) == np.asarray(b_k["ptr"])).mean()
    assert agree > 0.99  # float-order ties may flip the rare equidistant row


def test_cluster_sharded_single_device_matches_serial(cce_state):
    cce, params, buffers = cce_state
    mesh = make_mesh((1,), ("data",))
    p_s, b_s = cce.cluster_sharded(jax.random.PRNGKey(6), params, buffers, mesh)
    p_r, b_r = cce.cluster(jax.random.PRNGKey(6), params, buffers)
    np.testing.assert_allclose(
        np.asarray(p_s["tables"]), np.asarray(p_r["tables"]), rtol=1e-5, atol=1e-6
    )
    agree = (np.asarray(b_s["ptr"]) == np.asarray(b_r["ptr"])).mean()
    assert agree > 0.99
    np.testing.assert_array_equal(np.asarray(b_s["hs"]), np.asarray(b_r["hs"]))


def test_sharded_transition_traces_once(monkeypatch, cce_state):
    """The jitted ``cluster_sharded`` program is built once per table and
    mesh: a second transition re-runs it without a new trace.  The state
    starts on the mesh, as a trainer's state comes back from its step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train import transition

    cce, params, buffers = cce_state
    mesh = make_mesh((1,), ("data",))
    params, buffers = jax.device_put((params, buffers), NamedSharding(mesh, P()))
    traces = []
    orig = CCE.cluster_sharded

    def spy(self, *a, **kw):
        traces.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(CCE, "cluster_sharded", spy)
    transition._cluster_program.cache_clear()
    p1, b1, _ = transition.transition_table(
        cce, jax.random.PRNGKey(6), params, buffers,
        mesh=mesh, shard_axis="data", use_kernel=False,
    )
    p2, b2, _ = transition.transition_table(
        cce, jax.random.PRNGKey(7), p1, b1,
        mesh=mesh, shard_axis="data", use_kernel=False,
    )
    transition._cluster_program.cache_clear()
    assert len(traces) == 1
    assert int(b2["epoch"]) == int(b1["epoch"]) + 1


# --- moment remap ------------------------------------------------------------


def test_remap_moments_is_cluster_mean(cce_state):
    cce, params, buffers = cce_state
    moments = {
        "tables": jax.random.normal(jax.random.PRNGKey(4), params["tables"].shape)
    }
    _, b2 = cce.cluster(jax.random.PRNGKey(5), params, buffers)
    rm = cce.remap_moments(moments, buffers, b2)
    mt = np.asarray(rm["tables"])
    assert float(np.abs(mt[:, 1]).max()) == 0.0  # fresh helper: zero moments
    # reference: materialize per-id moments under the OLD pointers, then
    # mean per NEW cluster
    per_id = np.asarray(cce.materialize(moments, buffers, jnp.arange(cce.d1)))
    ptr = np.asarray(b2["ptr"])
    for i in range(cce.c):
        for j in range(cce.k):
            sel = per_id[i][ptr[i] == j]
            want = sel.mean(0) if len(sel) else np.zeros(cce.dsub, np.float32)
            np.testing.assert_allclose(mt[i, 0, j], want, rtol=1e-5, atol=1e-6)
    # streaming the remap changes nothing (up to f32 accumulation order)
    rm2 = cce.remap_moments(moments, buffers, b2, chunk_size=113)
    np.testing.assert_allclose(np.asarray(rm2["tables"]), mt, rtol=1e-4, atol=1e-5)


def test_remap_opt_state_policies():
    opt = {"m": {"w": jnp.ones(3)}, "t": jnp.zeros((), jnp.int32) + 5}
    out = remap_opt_state(opt, lambda mom, slot: jax.tree.map(lambda x: 2 * x, mom))
    assert float(out["m"]["w"][0]) == 2.0
    assert int(out["t"]) == 5  # scalar slots untouched: bias correction continuous
    assert remap_opt_state(opt, None, policy="keep") is opt
    assert remap_opt_state({}, None) == {}  # plain SGD
    with pytest.raises(ValueError):
        remap_opt_state(opt, None, policy="bogus")


def test_remap_opt_state_carries_adagrad_accumulators():
    """Adagrad's per-element accumulator is a moment slot: a transition
    remaps (or resets) it with the table it belongs to."""
    from repro.optim import adagrad

    opt = adagrad(initial_accumulator=0.5).init({"w": jnp.ones(3)})
    out = remap_opt_state(opt, lambda mom, slot: jax.tree.map(lambda x: 2 * x, mom))
    assert float(out["a"]["w"][0]) == 1.0


def test_cluster_tables_remaps_and_resets():
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    opt = jax.tree.map(
        lambda x: jnp.full_like(x, 0.5), sgd(momentum=0.9).init(params)
    )
    p2, b2, opt2 = dlrm.cluster_tables(
        jax.random.PRNGKey(1), params, buffers, cfg, opt
    )
    # non-embedding moments flow through untouched
    np.testing.assert_array_equal(
        np.asarray(opt2["m"]["bottom"][0]["w"]),
        np.asarray(opt["m"]["bottom"][0]["w"]),
    )
    for i in range(cfg.n_sparse):
        if isinstance(cfg.table(i), CCE):
            m = np.asarray(coll.feature_params(opt2["m"]["emb"], i)["tables"])
            assert float(np.abs(m[:, 1]).max()) == 0.0  # helper slab zeroed
            # per-id moment is 0.5 (main) + 0.5 (helper) = 1.0 everywhere, so
            # every non-empty cluster's remapped moment is exactly 1.0
            ptr = np.asarray(coll.feature_buffers(b2["emb"], i)["ptr"])
            for col in range(ptr.shape[0]):
                nonempty = np.unique(ptr[col])
                np.testing.assert_allclose(m[col, 0, nonempty], 1.0, rtol=1e-6)
    _, _, opt3 = dlrm.cluster_tables(
        jax.random.PRNGKey(1), params, buffers, cfg, opt, policy="reset"
    )
    for i in range(cfg.n_sparse):
        if isinstance(cfg.table(i), CCE):
            m3 = np.asarray(coll.feature_params(opt3["m"]["emb"], i)["tables"])
            assert float(np.abs(m3).max()) == 0.0


# --- frequency-weighted k-means sampling -------------------------------------


def test_id_frequency_tracker():
    from repro.train.freq import IdFrequencyTracker

    tr = IdFrequencyTracker((10, 5))
    assert tr.sample_ids(0, 0, 8) is None  # nothing observed: uniform fallback
    tr.observe({"sparse": np.array([[1, 2], [1, 3], [7, 2]])})
    tr.observe({"sparse": np.array([[1, 2]])})
    assert tr.counts[0][1] == 3 and tr.counts[0][7] == 1
    s = tr.sample_ids(42, 0, 1000)
    assert set(np.unique(s)) <= {1, 7}
    # frequency-weighted: id 1 (3 of 4 observations) dominates the sample
    assert (s == 1).mean() > 0.5
    np.testing.assert_array_equal(s, tr.sample_ids(42, 0, 1000))  # deterministic
    # checkpoint round-trip
    tr2 = IdFrequencyTracker((10, 5))
    tr2.load_state_tree(tr.state_tree())
    np.testing.assert_array_equal(tr2.counts[0], tr.counts[0])


def test_points_from_counts_is_weighted_not_sampled():
    from repro.train.freq import points_from_counts

    counts = np.array([0, 3, 0, 1, 5, 0])
    ids, w = points_from_counts(counts, 10, seed=0)
    np.testing.assert_array_equal(ids, [1, 3, 4])  # every observed id ONCE
    np.testing.assert_array_equal(w, [3.0, 1.0, 5.0])  # counts ARE the weights
    assert points_from_counts(np.zeros(4), 10, 0) is None  # uniform fallback
    # over-cap: stratified, deterministic, unbiased — the head enters
    # exactly, the uniform tail is Horvitz-Thompson-inflated
    big = np.arange(100)  # id i observed i times; ids 96..99 are the head
    ids1, w1 = points_from_counts(big, 10, seed=7)
    ids2, w2 = points_from_counts(big, 10, seed=7)
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(w1, w2)
    assert len(ids1) == len(np.unique(ids1)) == 10
    head = np.arange(95, 100)  # top n/2 counts included with certainty
    assert set(head) <= set(ids1)
    lut = dict(zip(ids1, w1))
    for i in head:
        assert lut[i] == big[i]  # exact counts for the head
    # tail: count * (|rest| / n_tail); 99 observed ids - 5 head = 94 rest
    for i in set(ids1) - set(head):
        np.testing.assert_allclose(lut[i], big[i] * 94 / 5)
    # E[total weight] == total observed mass (unbiasedness, in expectation)
    tots = [points_from_counts(big, 10, seed=s)[1].sum() for s in range(300)]
    np.testing.assert_allclose(np.mean(tots), big.sum(), rtol=0.05)


def test_weighted_lloyd_equals_multiset_lloyd():
    """A weighted Lloyd iteration on unique points IS the unweighted
    iteration on the multiset — the exact form of the epoch-boundary
    sample that with-replacement draws only approximate."""
    from repro.core import kmeans as km

    x = jax.random.normal(jax.random.PRNGKey(0), (12, 4))
    w = jnp.asarray([3.0, 1, 2, 1, 1, 4, 1, 2, 1, 1, 5, 1])
    c0 = jax.random.normal(jax.random.PRNGKey(1), (3, 4))
    c_w, _, in_w = km._lloyd_step(x, c0, 3, weights=w)
    c_d, _, in_d = km._lloyd_step(jnp.repeat(x, w.astype(int), axis=0), c0, 3)
    np.testing.assert_allclose(np.asarray(c_w), np.asarray(c_d), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(in_w), float(in_d), rtol=1e-4)


def test_weighted_kmeans_follows_the_mass():
    """Centroids must track the weight, not the point count: many light
    points vs one heavy point."""
    from repro.core import kmeans as km

    light = jax.random.normal(jax.random.PRNGKey(2), (63, 2)) * 0.05
    heavy = jnp.array([[10.0, 10.0]])
    x = jnp.concatenate([light, heavy])
    w = jnp.concatenate([jnp.ones(63), jnp.asarray([1000.0])])
    res = km.kmeans(jax.random.PRNGKey(3), x, 2, niter=20, weights=w)
    d_heavy = np.linalg.norm(np.asarray(res.centroids) - np.array([10, 10]), axis=1)
    assert d_heavy.min() < 0.1  # one centroid sits ON the heavy point


def test_transition_uses_count_weighted_sample(cce_state, monkeypatch):
    """With a histogram, cluster() must receive the UNIQUE observed ids
    plus weights (not a with-replacement multiset)."""
    from repro.train.transition import transition_table

    cce, params, buffers = cce_state
    counts = np.zeros(cce.d1)
    counts[[7, 13, 99]] = [5, 1, 2]
    seen = {}
    orig = CCE.cluster

    def spy(self, key, p, b, **kw):
        seen.update(kw)
        return orig(self, key, p, b, **kw)

    monkeypatch.setattr(CCE, "cluster", spy)
    transition_table(cce, jax.random.PRNGKey(0), params, buffers, counts=counts)
    np.testing.assert_array_equal(np.asarray(seen["sample_ids"]), [7, 13, 99])
    np.testing.assert_array_equal(np.asarray(seen["sample_weights"]), [5.0, 1.0, 2.0])


# --- sharded full-vocab assignment (forced multi-device) ----------------------


def test_assign_all_sharded_matches_serial_on_one_device(cce_state):
    cce, params, buffers = cce_state
    mesh = make_mesh((1,), ("data",))
    cents = jax.random.normal(jax.random.PRNGKey(1), (cce.c, cce.k, cce.dsub))
    a_serial = cce.assign_all(params, buffers, cents, use_kernel=False)
    a_shard = cce.assign_all_sharded(
        params, buffers, cents, mesh, chunk_size=97, use_kernel=False
    )
    np.testing.assert_array_equal(np.asarray(a_serial), np.asarray(a_shard))


@pytest.mark.slow
def test_cluster_sharded_on_forced_four_device_host():
    """The whole sharded transition — distributed weighted k-means AND the
    sharded full-vocab assignment — on a real 4-device (forced host) mesh,
    in a subprocess so the flag is set before jax initializes."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core.cce import CCE

        assert jax.device_count() == 4, jax.devices()
        cce = CCE(d1=303, d2=16, k=8, c=2, seed_salt=1)
        params, buffers = cce.init(jax.random.PRNGKey(0))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 303, 256))
        w = jnp.asarray(rng.integers(1, 5, 256), jnp.float32)
        p_s, b_s = cce.cluster_sharded(
            jax.random.PRNGKey(3), params, buffers, mesh,
            sample_ids=ids, sample_weights=w, chunk_size=50,
        )
        # after the transition the main table IS the centroids, so the
        # sharded full-vocab assignment must reproduce a serial assign
        # against them (up to float-tie flips)
        cents = p_s["tables"][:, 0].astype(jnp.float32)
        want = np.asarray(cce.assign_all(params, buffers, cents, use_kernel=False))
        got = np.asarray(b_s["ptr"])
        assert got.shape == want.shape == (2, 303)
        assert (got == want).mean() > 0.99, (got != want).sum()
        assert float(np.abs(np.asarray(p_s["tables"][:, 1])).max()) == 0.0
        print("MULTIDEVICE-OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.join(os.path.dirname(__file__), "..", "src"),
                      env.get("PYTHONPATH")])
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MULTIDEVICE-OK" in r.stdout


# --- the Trainer protocol ----------------------------------------------------


def _setup(seed=0, cap=512):
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=cap)
    params, buffers = dlrm.init(jax.random.PRNGKey(seed), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=seed), 32
    )
    return cfg, step, state, static, data


def test_cce_buffers_are_fully_dynamic():
    """The transition rewrites ptr, hs AND epoch; all three must ride the
    dynamic ebuf through the jitted step — a static (python-int) leaf would
    leave the step training against pre-transition hash functions (the
    seed's silent regression)."""
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    _, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    _, (treedef, static_items) = split_buffers(buffers)
    assert static_items == (), static_items


def test_trainer_threads_opt_through_transition():
    cfg, step, state, static, data = _setup()

    def cluster_fn(key, p, b, opt):
        return dlrm.cluster_tables(key, p, b, cfg, opt)

    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 cluster_fn=cluster_fn, cluster_every=10, cluster_max=1)
    tr.run(10)  # the transition fires after the final step
    assert tr.clusters_done == 1
    for i in range(cfg.n_sparse):
        if isinstance(cfg.table(i), CCE):
            m = np.asarray(
                cfg.collection.feature_params(tr.state.opt["m"]["emb"], i)["tables"]
            )
            assert float(np.abs(m[:, 1]).max()) == 0.0  # no stale helper moments


def test_restart_exact_across_transition(tmp_path):
    """Crash AFTER a transition, restore from the pre-transition
    checkpoint, replay — the transition (clustering, fresh hashes, moment
    remap) re-runs deterministically and the final state is bitwise equal
    to the uninterrupted run."""

    from repro.train.freq import IdFrequencyTracker

    def make(cfg, tracker):
        def cluster_fn(key, p, b, opt):
            return dlrm.cluster_tables(key, p, b, cfg, opt,
                                       id_counts=tracker.counts)

        return dict(cluster_fn=cluster_fn, cluster_every=6, cluster_max=2,
                    id_tracker=tracker, seed=1)

    def run(fail: bool):
        cfg, step, state, static, data = _setup(seed=1)
        tracker = IdFrequencyTracker(cfg.vocab_sizes)
        tr = Trainer(
            jax.jit(step, donate_argnums=(0,)), state, static, data,
            ckpt_dir=str(tmp_path / ("a" if fail else "b")), ckpt_every=5,
            failures=FailureInjector((8,)) if fail else None,
            **make(cfg, tracker),
        )
        if fail:
            with pytest.raises(RuntimeError):
                tr.run(12)
            cfg2, step2, _, static2, _ = _setup(seed=1)
            tracker2 = IdFrequencyTracker(cfg2.vocab_sizes)
            tr2 = Trainer(
                jax.jit(step2, donate_argnums=(0,)), tr.state, static2,
                clickstream_batches(
                    ClickstreamConfig(vocab_sizes=cfg2.vocab_sizes, seed=1),
                    32, start_step=5,
                ),
                ckpt_dir=str(tmp_path / "a"), **make(cfg2, tracker2),
            )
            restored = tr2.restore_latest()
            assert restored == 5 and tr2.clusters_done == 0
            assert int(tracker2.counts[0].sum()) == 5 * 32  # histograms resumed
            tr2.run(12 - restored)
            return tr2.state
        tr.run(12)
        return tr.state

    s_fail = run(True)
    s_clean = run(False)
    for a, b in zip(jax.tree.leaves(s_fail.params), jax.tree.leaves(s_clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_fail.opt), jax.tree.leaves(s_clean.opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_three_arg_cluster_fn_still_supported():
    cfg, step, state, static, data = _setup()

    def cluster_fn(key, p, b):
        return dlrm.cluster_tables(key, p, b, cfg)

    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 cluster_fn=cluster_fn, cluster_every=5, cluster_max=1)
    hist = tr.run(6)
    assert tr.clusters_done == 1 and np.isfinite(hist[-1]["loss"])
