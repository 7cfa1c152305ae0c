"""DLRM serve engine: cache-vs-supertable bit-exactness, launch counts,
staleness enforcement, churn refresh, micro-batching — DESIGN.md §11."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dlrm_criteo import reduced, reduced_stream
from repro.models import dlrm
from repro.obs.runlog import RunLog, read_runlog
from repro.serve.dlrm import (
    DLRMServeEngine,
    HotCache,
    MicroBatcher,
    ServeRequest,
    StaleCacheError,
)
from repro.stream.trigger import head_churn

B = 8


@pytest.fixture(scope="module")
def state():
    cfg = reduced()
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    tracker = dlrm.make_id_tracker(cfg, reduced_stream())
    rng = np.random.default_rng(0)
    # warm the sketches so the SpaceSaving heads are populated
    warm = np.stack(
        [rng.integers(0, v, 256) for v in cfg.vocab_sizes], axis=1
    )
    tracker.observe({"sparse": warm})
    # jit-compiled reference: the serve programs are jitted, and XLA
    # fusion may round differently from the eager path — jit vs jit is
    # the bit-exactness contract
    fwd = jax.jit(
        lambda p, b, batch: dlrm.forward(p, b, cfg, batch),
    )

    def ref(p, b, dense, sparse):
        return np.asarray(
            fwd(p, b, {"dense": jnp.asarray(dense), "sparse": jnp.asarray(sparse)})
        )

    return cfg, params, buffers, tracker, warm, ref


def _engine(state, **kw):
    cfg, params, buffers, tracker, _, _ = state
    kw.setdefault("tracker", tracker)
    kw.setdefault("max_batch", B)
    kw.setdefault("use_kernel", False)
    return DLRMServeEngine(params, buffers, cfg, **kw)


def _batch(state, rng, *, head=False):
    cfg = state[0]
    dense = rng.normal(size=(B, cfg.n_dense)).astype(np.float32)
    if head:
        return dense, None
    sparse = np.stack(
        [rng.integers(0, v, B) for v in cfg.vocab_sizes], axis=1
    )
    return dense, sparse


def _head_batch(cache, cfg, n):
    """ids drawn entirely from the cached head -> fully-hit batch."""
    cols = []
    for f in range(cfg.n_sparse):
        ids = cache.ids.get(f)
        assert ids is not None and ids.size, f"feature {f} not cached"
        cols.append(ids[np.arange(n) % ids.size])
    return np.stack(cols, axis=1)


def _miss_batch(cache, cfg, rng, n):
    """every id OUTSIDE the cached head -> fully-cold batch."""
    cols = []
    for f, v in enumerate(cfg.vocab_sizes):
        cand = np.setdiff1d(np.arange(v), cache.ids.get(f, np.empty(0)))
        cols.append(cand[rng.integers(0, cand.size, n)])
    return np.stack(cols, axis=1)


def test_hit_batch_is_exact_and_launch_free(state):
    cfg, params, buffers = state[0], state[1], state[2]
    eng = _engine(state)
    rng = np.random.default_rng(1)
    dense, _ = _batch(state, rng, head=True)
    sparse = _head_batch(eng.cache, cfg, B)
    got = eng.predict(dense, sparse)
    assert np.array_equal(got, state[5](params, buffers, dense, sparse))
    assert eng.counters["n_launches"] == 0
    assert eng.counters["n_hit_batches"] == 1
    assert eng.counters["n_id_hits"] == B * cfg.n_sparse


def test_mixed_batch_is_exact_with_one_launch(state):
    cfg, params, buffers = state[0], state[1], state[2]
    eng = _engine(state)
    rng = np.random.default_rng(2)
    dense, _ = _batch(state, rng, head=True)
    sparse = _head_batch(eng.cache, cfg, B)
    sparse[::2] = _miss_batch(eng.cache, cfg, rng, B)[::2]
    got = eng.predict(dense, sparse)
    assert np.array_equal(got, state[5](params, buffers, dense, sparse))
    assert eng.counters["n_launches"] == 1
    # half the requests answered purely from cache
    assert 0 < eng.counters["n_id_hits"] < B * cfg.n_sparse


def test_uncached_engine_matches_forward(state):
    cfg, params, buffers = state[0], state[1], state[2]
    eng = _engine(state, cache=False)
    rng = np.random.default_rng(3)
    dense, sparse = _batch(state, rng)
    got = eng.predict(dense, sparse)
    assert np.array_equal(got, state[5](params, buffers, dense, sparse))
    assert eng.counters["n_launches"] == 1
    assert eng.counters["n_id_hits"] == 0


def test_ragged_batch_pads_to_bucket(state):
    cfg, params, buffers = state[0], state[1], state[2]
    eng = _engine(state)
    rng = np.random.default_rng(4)
    dense, sparse = _batch(state, rng)
    n = 3  # < max_batch: engine pads to the bucket, answers stay exact
    got = eng.predict(dense[:n], sparse[:n])
    assert got.shape == (n,)
    assert np.array_equal(
        got, state[5](params, buffers, dense[:n], sparse[:n])
    )


def test_cache_exact_across_clustering_transition(state):
    cfg, params, buffers, tracker = state[:4]
    eng = _engine(state)
    p2, b2 = dlrm.cluster_tables(
        jax.random.PRNGKey(7), params, buffers, cfg,
        id_counts=tracker.counts, use_kernel=False,
    )
    eng.update_state(p2, b2)  # refreshes the cache at the transition
    rng = np.random.default_rng(5)
    dense, _ = _batch(state, rng, head=True)
    sparse = _head_batch(eng.cache, cfg, B)
    got = eng.predict(dense, sparse)
    assert np.array_equal(got, state[5](p2, b2, dense, sparse))
    assert eng.counters["n_refreshes"] == 2  # init + transition


def test_stale_cache_is_refused_not_served(state):
    cfg, params, buffers, tracker = state[:4]
    eng = _engine(state)
    sparse = _head_batch(eng.cache, cfg, B)
    dense = np.zeros((B, cfg.n_dense), np.float32)
    p2, b2 = dlrm.cluster_tables(
        jax.random.PRNGKey(8), params, buffers, cfg,
        id_counts=tracker.counts, use_kernel=False,
    )
    # serving across the transition WITHOUT a refresh must raise: the
    # cache still holds pre-transition decoded rows
    eng.update_state(p2, b2, refresh_cache=False)
    with pytest.raises(StaleCacheError):
        eng.predict(dense, sparse)
    # an explicit refresh clears the condition
    eng.refresh_cache()
    got = eng.predict(dense, _head_batch(eng.cache, cfg, B))
    assert got.shape == (B,)


def test_head_churn_triggers_refresh(state):
    cfg, params, buffers = state[0], state[1], state[2]
    # private tracker: this test mutates head state
    tracker = dlrm.make_id_tracker(cfg, reduced_stream())
    rng = np.random.default_rng(6)
    lo = np.stack([rng.integers(0, 50, 512) for _ in cfg.vocab_sizes], 1)
    tracker.observe({"sparse": lo})
    eng = _engine(state, tracker=tracker)
    old_ids = {f: ids.copy() for f, ids in eng.cache.ids.items()}
    assert eng.maybe_refresh() == pytest.approx(0.0)  # no churn yet
    assert eng.counters["n_refreshes"] == 1
    # hammer a disjoint id range until the SpaceSaving head turns over
    hi = np.stack(
        [50 + rng.integers(0, 50, 4096) for _ in cfg.vocab_sizes], 1
    )
    tracker.observe({"sparse": hi})
    churn = eng.maybe_refresh()
    assert churn is not None and churn >= eng.churn_threshold
    assert eng.counters["n_refreshes"] == 2
    assert any(
        not np.array_equal(eng.cache.ids[f], old_ids[f]) for f in old_ids
    )
    # post-refresh answers are exact on the NEW head
    dense = np.zeros((B, cfg.n_dense), np.float32)
    sparse = _head_batch(eng.cache, cfg, B)
    assert np.array_equal(
        eng.predict(dense, sparse),
        state[5](params, buffers, dense, sparse),
    )


def test_microbatcher_latency_budget():
    t = [0.0]
    mb = MicroBatcher(max_batch=4, latency_budget_s=0.010, clock=lambda: t[0])
    r = lambda i: ServeRequest(uid=i, dense=np.zeros(2), sparse=np.zeros(3))
    mb.submit(r(0))
    assert not mb.ready()  # under budget, under max_batch: hold
    t[0] = 0.005
    assert not mb.ready()
    t[0] = 0.011  # oldest request exceeded the budget: dispatch
    assert mb.ready()
    assert [q.uid for q in mb.take()] == [0]
    for i in range(1, 6):
        mb.submit(r(i))
    assert mb.ready()  # full batch dispatches immediately
    assert len(mb.take()) == 4
    assert len(mb) == 1


def test_request_path_events_and_histograms(state, tmp_path):
    cfg, params, buffers = state[0], state[1], state[2]
    log_path = tmp_path / "serve.jsonl"
    with RunLog(log_path, manifest={"config": "serve-test"}) as rl:
        eng = _engine(state, run_log=rl, latency_budget_s=0.0)
        rng = np.random.default_rng(9)
        dense, sparse = _batch(state, rng)
        hit_sparse = _head_batch(eng.cache, cfg, B)
        for i in range(B):
            eng.submit(ServeRequest(uid=i, dense=dense[i], sparse=hit_sparse[i]))
        results = eng.drain()
        for i in range(3):
            eng.submit(
                ServeRequest(uid=B + i, dense=dense[i], sparse=sparse[i])
            )
        results += eng.drain()
        stats = eng.flush_stats()
    assert len(results) == B + 3
    assert all(r.cache_hit for r in results[:B])
    assert stats["n_requests"] == B + 3
    assert 0 < stats["hit_rate_requests"] <= 1
    assert stats["launches_per_batch"] < 1.0  # hit batches skipped theirs
    recs = read_runlog(log_path)
    reqs = [r for r in recs if r["event"] == "request"]
    assert len(reqs) == B + 3
    assert all("cache_hit" in r and r["latency_s"] >= 0 for r in reqs)
    refreshes = [r for r in recs if r["event"] == "cache_refresh"]
    assert [r["reason"] for r in refreshes] == ["init"]
    hists = [r for r in recs if r["event"] == "latency_hist"]
    assert {h["label"] for h in hists} == {
        "serve-dlrm", "serve-dlrm-hit", "serve-dlrm-cold",
    }
    # the jax-free summarizer picks up the serve-cache sections
    from repro.obs.summary import format_summary, summarize_dict

    s = summarize_dict(recs)
    assert s["serve_cache"]["n_requests"] == B + 3
    assert s["cache_refreshes"][0]["reason"] == "init"
    assert "serve cache:" in format_summary(recs)


def test_logits_identical_with_and_without_cache(state):
    """The cache is a pure latency optimization: cached and uncached
    engines agree bitwise on identical traffic."""
    cfg = state[0]
    cached, uncached = _engine(state), _engine(state, cache=False)
    rng = np.random.default_rng(10)
    dense, sparse = _batch(state, rng)
    sparse[:4] = _head_batch(cached.cache, cfg, 4)
    assert np.array_equal(
        cached.predict(dense, sparse), uncached.predict(dense, sparse)
    )


def test_rows_masked_masks_exactly_the_hit_features(state):
    cfg = state[0]
    eng = _engine(state)
    rng = np.random.default_rng(11)
    _, sparse = _batch(state, rng)
    coll = cfg.collection
    skip = rng.random((B, cfg.n_sparse)) < 0.5
    rows = eng.translator.rows(sparse)
    masked = eng.translator.rows_masked(sparse, skip)
    col_owner = coll.rows_col_feature
    assert col_owner.shape == (coll.rows_n_cols,)
    for b in range(B):
        for c in range(coll.rows_n_cols):
            if skip[b, col_owner[c]]:
                assert (masked[b, c] == -1).all()
            else:
                assert np.array_equal(masked[b, c], rows[b, c])


def test_head_churn_metric():
    assert head_churn(np.array([1, 2, 3]), np.array([3, 2, 1])) == 0.0
    assert head_churn(np.array([1, 2]), np.array([3, 4])) == 1.0
    assert head_churn(np.array([1, 2, -1]), np.array([2, 3])) == pytest.approx(
        2 / 3
    )
    assert head_churn(np.array([]), np.array([])) == 0.0
    assert head_churn(np.array([]), np.array([1])) == 1.0


def test_export_heads_names_the_hot_ids(state):
    cfg, _, _, tracker = state[:4]
    heads = tracker.export_heads()
    assert set(heads) == set(tracker.tracked)
    capped = tracker.export_heads(4)
    for f, ids in heads.items():
        assert ids.size > 0
        assert capped[f].size <= 4
        assert np.array_equal(capped[f], ids[:4])


def test_hot_cache_build_drops_bad_ids(state):
    cfg, params, buffers = state[0], state[1], state[2]
    coll = cfg.collection
    cache = HotCache.build(
        coll, params["emb"], buffers["emb"],
        {0: np.array([5, 5, -3, 10**9, 2])},
    )
    assert np.array_equal(cache.ids[0], [2, 5])
    assert cache.n_slots == 2
    slots, hit = cache.slots(np.array([[5, 0, 0, 0, 0], [7, 0, 0, 0, 0]]))
    assert hit[0, 0] and not hit[1, 0]
    assert slots[0, 0] == 1 and slots[1, 0] == -1

def test_engine_refuses_bags():
    """Serving stays one id a feature until a serve cell needs bags."""
    import dataclasses

    cfg = dataclasses.replace(reduced(emb_method="cce", cap=512), bag_sizes=(2, 1, 1, 1, 1))
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="bag_sizes"):
        DLRMServeEngine(params, buffers, cfg)
