"""The streaming-statistics subsystem (repro/stream/, DESIGN.md §5):

* count-min sketch invariants — conservative-update overestimate, the
  count-mean unbiased tail estimator, device-cell/host-cell agreement,
* SpaceSaving head — top ids of a Zipf stream tracked with exact counts,
* decay/window semantics — estimates scale, recency wins,
* the k-means point provider — exact head + HT tail, float-count
  cleanliness (satellite: no silent int truncation on decayed counts),
  and the property test that the HT subsample stays unbiased under decay,
* tracker memory — O(sketch), independent of vocabulary, asserted at a
  10M-row config,
* trigger policy edge cases — empty stream, single-id stream, exactly
  one fire per collapse, drift firing, restart-exact trigger state,
* Trainer integration — adaptive transitions, restart-exact resume with
  sketch + trigger, and legacy DENSE id_counts checkpoints migrating
  into the sketch tracker bit-for-bit on the head ids.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import dlrm_criteo
from repro.data import ClickstreamConfig, clickstream_batches
from repro.models import dlrm
from repro.optim import sgd
from repro.stream import (
    ClusterTrigger,
    CountMinSketch,
    FeatureSketch,
    IdFrequencyTracker,
    SketchFrequencyTracker,
    StreamConfig,
    points_from_counts,
    sample_from_counts,
)
from repro.train.loop import (
    FailureInjector,
    Trainer,
    init_state,
    make_train_step,
    split_buffers,
)


def _zipf_stream(vocab=50_000, n=60_000, a=1.3, seed=0):
    return np.random.default_rng(seed).zipf(a, n) % vocab


# --- count-min sketch ---------------------------------------------------------


def test_cms_conservative_overestimate_invariant():
    ids = _zipf_stream()
    cms = CountMinSketch(width=1 << 10, depth=4, seed=3)
    for lo in range(0, ids.size, 4096):
        u, c = np.unique(ids[lo : lo + 4096], return_counts=True)
        cms.add(u, c)
    true = np.bincount(ids)
    probe = np.unique(ids)[:2000]
    est = cms.estimate(probe)
    assert (est >= true[probe] - 1e-9).all()  # never underestimates
    assert cms.total == pytest.approx(ids.size)


def test_cms_corrected_estimator_beats_min_on_tail():
    """At narrow width (heavy collision pressure) the collision-corrected
    estimate must carry LESS tail bias than the min-estimate, on both the
    conservative-update (host) and plain-add (device fold) paths — and
    never exceed the min upper bound."""
    ids = _zipf_stream()
    true = np.bincount(ids)
    u = np.unique(ids)
    tail = u[true[u] <= 3]

    def errs(cms):
        e_min = float(np.mean(cms.estimate(tail) - true[tail]))
        e_ub = float(np.mean(cms.estimate_unbiased(tail) - true[tail]))
        assert (cms.estimate_unbiased(tail) <= cms.estimate(tail) + 1e-9).all()
        return e_min, e_ub

    cu = CountMinSketch(width=1 << 10, depth=4, seed=3)
    for lo in range(0, ids.size, 2048):
        uu, cc = np.unique(ids[lo : lo + 2048], return_counts=True)
        cu.add(uu, cc)
    e_min, e_ub = errs(cu)
    assert abs(e_ub) < abs(e_min)

    plain = CountMinSketch(width=1 << 10, depth=4, seed=3)
    cells = plain.cells(ids)
    delta = np.zeros((4, 1 << 10))
    for r in range(4):
        np.add.at(delta[r], cells[r], 1)
    plain.add_cells(delta)
    e_min, e_ub = errs(plain)
    assert abs(e_ub) < abs(e_min)


def test_cms_device_cells_match_host_cells():
    from repro.stream.device import make_cell_counter

    cms = CountMinSketch(width=1 << 9, depth=3, seed=7)
    counter = make_cell_counter([cms], [0])
    ids = np.random.default_rng(1).integers(0, 1_000_000, 4096)
    delta = np.asarray(counter(jnp.asarray(ids[:, None], jnp.int32)))[0]
    ref = np.zeros((3, 1 << 9), np.int64)
    cells = cms.cells(ids)
    for r in range(3):
        np.add.at(ref[r], cells[r], 1)
    np.testing.assert_array_equal(ref, delta)
    # and folding the delta gives the plain-CMS state: estimate still an
    # overestimate of every id's true count
    cms.add_cells(delta)
    true = np.bincount(ids)
    probe = np.unique(ids)
    assert (cms.estimate(probe) >= true[probe]).all()


# --- heavy hitters ------------------------------------------------------------


def test_spacesaving_head_is_exact_on_zipf_top():
    ids = _zipf_stream(seed=5)
    fs = FeatureSketch(width=1 << 11, depth=4, heavy=64, ring=2048, seed=0)
    for lo in range(0, ids.size, 2048):
        fs.observe(ids[lo : lo + 2048])
    true = np.bincount(ids)
    top = np.argsort(true)[::-1][:16]
    h_ids, h_cnt = fs.hh.head()
    assert np.isin(top, h_ids).all()  # the true top-16 are all resident
    lut = dict(zip(h_ids.tolist(), h_cnt.tolist()))
    for i in top.tolist():  # ...with their EXACT stream counts
        assert lut[i] == true[i]
    # estimates never underestimate, resident or not
    probe = np.unique(ids)[:1000]
    assert (fs.estimate(probe) >= true[probe] - 1e-9).all()


@pytest.mark.parametrize("shape", [(1, 0), (1, 1), (1, 300), (5, 64), (17, 2048)])
def test_count_rows_matches_unique_per_row(shape):
    """One row-wise count gives each row what ``np.unique`` gives it."""
    from repro.stream.sketch import count_rows

    rng = np.random.default_rng(sum(shape))
    ids = (rng.zipf(1.2, shape) % 1000).astype(np.int32)
    uids, counts, bounds = count_rows(np.asfortranarray(ids))  # as the fold's transpose
    assert bounds.shape == (shape[0] + 1,) and counts.dtype == np.float64
    for f in range(shape[0]):
        u, c = np.unique(ids[f], return_counts=True)
        np.testing.assert_array_equal(uids[bounds[f]:bounds[f + 1]], u)
        np.testing.assert_array_equal(counts[bounds[f]:bounds[f + 1]], c)
    assert bounds[-1] == uids.size


def test_spacesaving_counts_admissions_and_evictions():
    """The running counters the fold's ``fold-heads`` span reports."""
    from repro.stream import SpaceSaving

    cms = CountMinSketch(width=1 << 8, depth=2, seed=0)
    hh = SpaceSaving(2)
    # two free slots fill; 7's estimate does not beat the minimum resident
    hh.offer(np.array([5, 6, 7]), np.array([3.0, 2.0, 1.0]), cms)
    assert (hh.admitted, hh.evicted) == (2, 0)
    # 8 evicts 6; 9 does not beat the new minimum (5 at 3.0)
    hh.offer(np.array([8, 9]), np.array([4.0, 2.5]), cms)
    assert (hh.admitted, hh.evicted) == (3, 1)
    assert sorted(hh.head()[0].tolist()) == [5, 8]
    assert (hh.candidates, hh.rebuilds) == (4, 0)


def _offer_oracle(hh, ids, ests, sketch):
    """SpaceSaving admission candidate by candidate, as ``offer`` did it
    before the one-pass rule: each candidate in descending estimate order
    takes a free slot, else evicts ``argmin`` of the counts if its
    estimate beats it, else ends the batch; any admission leaves the
    residency index to be rebuilt."""
    order = np.argsort(np.asarray(ests), kind="stable")[::-1]
    n0 = hh.n
    evicted_ids, evicted_cnt = [], []
    for j in order.tolist():
        i, est = int(ids[j]), float(ests[j])
        if hh.n < hh.capacity:
            hh.ids[hh.n], hh.counts[hh.n] = i, est
            hh.n += 1
            continue
        s = int(np.argmin(hh.counts))
        if est <= hh.counts[s]:
            break
        evicted_ids.append(int(hh.ids[s]))
        evicted_cnt.append(float(hh.counts[s]))
        hh.ids[s], hh.counts[s] = i, est
    hh.admitted += hh.n - n0 + len(evicted_ids)
    hh.evicted += len(evicted_ids)
    hh._dirty |= hh.n > n0 or bool(evicted_ids)
    if evicted_ids:
        sketch.raise_to(np.asarray(evicted_ids), np.asarray(evicted_cnt))


def _assert_index_fresh(hh):
    """The residency index equals a rebuild from the slots."""
    order = np.argsort(hh.ids[: hh.n], kind="stable")
    np.testing.assert_array_equal(hh._sorted_ids, hh.ids[: hh.n][order])
    np.testing.assert_array_equal(hh._sorted_slots, order)


def _assert_same_head(a, b):
    assert (a.n, a.admitted, a.evicted) == (b.n, b.admitted, b.evicted)
    np.testing.assert_array_equal(a.ids, b.ids)
    assert a.counts.tobytes() == b.counts.tobytes()


def _offer_case(name, rng):
    """-> (capacity, resident counts, batches of estimates) for one case;
    residents are ids 0.., candidates fresh ids above them."""
    def decayed(size, hi):  # integer counts after one decay: many ties
        return rng.integers(1, hi, size) * 0.95

    if name == "fill":
        return 8, [], [[3.0, 1.0, 2.0], [5.0, 4.0]]
    if name == "fill_then_evict":
        return 8, [3.0, 1.0, 4.0, 1.0, 5.0], [[2.0, 6.0, 0.5, 7.0, 1.0, 9.0, 3.5, 0.9]]
    if name == "none_beats_min":
        return 6, [5.0, 7.0, 6.0, 5.0, 9.0, 8.0], [[5.0, 4.0, 1.0, 5.0], []]
    if name == "all_admit":
        return 6, [1.0, 2.0, 3.0, 4.0, 5.0, 6.0], [[100.0, 50.0, 70.0]]
    if name == "more_than_capacity":
        return 8, [], [rng.integers(1, 50, 20) * 1.0, rng.integers(40, 90, 20) * 1.0]
    if name == "ties":
        return 64, decayed(64, 4), [decayed(40, 5) for _ in range(6)] + [np.full(30, 1.9)]
    if name == "empty":
        return 4, [2.0, 1.0], [[]]
    raise KeyError(name)


@pytest.mark.parametrize("case", ["fill", "fill_then_evict", "none_beats_min",
                                  "all_admit", "more_than_capacity", "ties", "empty"])
def test_one_pass_offer_matches_sequential_admission(case):
    """``SpaceSaving.offer`` admits in one pass exactly what the
    candidate-by-candidate rule admits: the same ids in the same slots
    with the same counts, the same admissions and evictions, the same
    sketch after the evicted counts are pushed back; and its in-place
    residency index equals a rebuild."""
    import copy

    from repro.stream import SpaceSaving

    rng = np.random.default_rng(7)
    cap, resident, batches = _offer_case(case, rng)
    hh = SpaceSaving(cap)
    ids = np.full(cap, -1, np.int64)
    ids[: len(resident)] = np.arange(len(resident))
    counts = np.zeros(cap)
    counts[: len(resident)] = resident
    hh.load_state_tree([ids, counts])
    hh.split_resident(np.zeros(0, np.int64))  # the lookup before each offer
    cms = CountMinSketch(width=1 << 6, depth=3, seed=1)
    cms.counters[:] = rng.integers(0, 6, cms.counters.shape) * 0.95
    ref, ref_cms = copy.deepcopy(hh), copy.deepcopy(cms)
    next_id = 1000
    for ests in batches:
        ests = np.asarray(ests, np.float64)
        cand = np.arange(next_id, next_id + ests.size)
        next_id += ests.size
        # offered a slot: the free slots' worth, then those over the minimum
        free = cap - ref.n
        floor = ref.counts[: ref.n].min() if ref.n else np.inf
        offered = hh.candidates + min(free, ests.size) + int(
            (np.sort(ests)[::-1][free:] > floor).sum())
        hh.offer(cand, ests, cms)
        assert hh.candidates == offered
        _offer_oracle(ref, cand, ests, ref_cms)
        _assert_same_head(hh, ref)
        assert cms.counters.tobytes() == ref_cms.counters.tobytes()
        _assert_index_fresh(hh)
    assert hh.rebuilds == 1  # the load's
    assert hh.candidates >= hh.admitted


def _host_delta(trk, sparse):
    """The (F_tracked, depth, width) cell delta the in-step counter
    computes, counted on the host."""
    sk = [trk.features[f].cms for f in trk.tracked]
    out = np.zeros((len(sk), sk[0].depth, sk[0].width), np.int32)
    for j, (f, cms) in enumerate(zip(trk.tracked, sk)):
        for r, cells in enumerate(cms.cells(sparse[:, f])):
            out[j, r] = np.bincount(cells, minlength=cms.width)
    return out


def _ingest_before(fs, raw_ids, into_sketch):
    """``FeatureSketch._ingest`` as it was before the batched fold: its own
    ``np.unique``, residency from a fresh sort of the slots, and the
    candidate-by-candidate admission."""
    raw_ids = np.asarray(raw_ids).reshape(-1)
    uids, ucnt = np.unique(raw_ids, return_counts=True)
    ucnt = ucnt.astype(np.float64)
    hh = fs.hh
    order = np.argsort(hh.ids[: hh.n], kind="stable")
    srt = np.append(hh.ids[: hh.n][order], -1)
    pos = np.minimum(np.searchsorted(srt[:-1], uids), max(hh.n - 1, 0))
    resident = srt[pos] == uids
    hh.counts[order[pos[resident]]] += ucnt[resident]
    absent_ids, absent_cnt = uids[~resident], ucnt[~resident]
    if into_sketch:
        fs.cms.add(absent_ids, absent_cnt)
    _offer_oracle(hh, absent_ids, fs.cms.estimate(absent_ids), fs.cms)
    fs.mass += float(ucnt.sum())
    fs._push_ring(raw_ids)


@pytest.mark.parametrize("path,seed", [("fold", 0), ("fold", 1), ("observe", 2)])
def test_batched_fold_matches_per_feature_ingest(path, seed):
    """Over a Zipf stream with window decays, on features whose heads
    never fill, churn, and churn hard: the tracker's fold (one count of
    every feature's ids, one-pass admission, in-place index) and its
    synchronous path leave the state, bit for bit, and the head order
    that the per-feature code with the sequential rule leaves.  The
    in-place index equals a rebuild after every batch, and nothing
    rebuilds it."""
    vocab = (40, 3000, 500_000)
    scfg = StreamConfig(width=1 << 10, depth=3, heavy=64, ring=256,
                        decay=0.95, window=16)
    new = SketchFrequencyTracker(vocab, scfg)
    old = SketchFrequencyTracker(vocab, scfg)
    old._fold_heads = lambda cols, into_sketch=False: [
        _ingest_before(old.features[f], cols[:, j], into_sketch)
        for j, f in enumerate(old.tracked)]
    rng = np.random.default_rng(seed)
    for _ in range(320):
        sparse = np.stack([rng.zipf(1.2, 256) % v for v in vocab], 1).astype(np.int32)
        kw = {"delta": _host_delta(new, sparse)} if path == "fold" else {}
        new.observe({"sparse": sparse}, **kw)
        old.observe({"sparse": sparse}, **kw)
        for f in new.tracked:
            _assert_index_fresh(new.features[f].hh)
            assert new.features[f].hh.rebuilds == 0
        for a, b in zip(new.state_tree(), old.state_tree()):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for f in new.tracked:
        for a, b in zip(new.features[f].hh.head(), old.features[f].hh.head()):
            np.testing.assert_array_equal(a, b)
    n = [new.features[f].hh.n for f in new.tracked]
    assert n[0] == 40 and n[1] == n[2] == 64  # one head never filled
    assert min(new.features[f].hh.evicted for f in new.tracked[1:]) > 100


@pytest.mark.parametrize("async_fold", [False, True])
def test_fold_updates_heads_only_through_their_methods(async_fold):
    """Heads whose ``bump`` and ``offer`` are replaced on the instance (how
    the benchmark plants a head that is never updated) stay as they were
    through the fold, while the rings and the mass still advance."""
    scfg = StreamConfig(width=1 << 9, depth=3, heavy=16, ring=128,
                        async_fold=async_fold)
    trk = SketchFrequencyTracker((100, 5000), scfg, tracked=(0, 1))
    rng = np.random.default_rng(3)

    def feed(n):
        for _ in range(n):
            sparse = np.stack([rng.zipf(1.3, 64) % 100, rng.zipf(1.3, 64) % 5000],
                              1).astype(np.int32)
            trk.observe({"sparse": sparse}, delta=_host_delta(trk, sparse))
        trk.flush()

    feed(3)
    heads = [trk.features[f].hh for f in trk.tracked]
    before = [(h.ids.copy(), h.counts.copy(), h.n) for h in heads]
    mass = [trk.features[f].mass for f in trk.tracked]
    for h in heads:
        h.bump = lambda slots, counts: None
        h.offer = lambda ids, ests, sketch: None
    feed(3)
    for h, (ids, counts, n) in zip(heads, before):
        assert h.n == n
        np.testing.assert_array_equal(h.ids, ids)
        np.testing.assert_array_equal(h.counts, counts)
    assert [trk.features[f].mass for f in trk.tracked] == [m + 3 * 64 for m in mass]


def test_decay_scales_and_recency_wins():
    fs = FeatureSketch(width=1 << 10, depth=4, heavy=8, ring=256, seed=0)
    old = np.repeat(np.arange(8), 50)  # old regime: ids 0..7, 50x each
    fs.observe(old)
    before = fs.estimate(np.arange(8)).copy()
    fs.decay(0.5)
    np.testing.assert_allclose(fs.estimate(np.arange(8)), before * 0.5)
    assert fs.mass == pytest.approx(old.size * 0.5)
    # new regime: ids 100..107 dominate after a few decayed windows
    for _ in range(6):
        fs.observe(np.repeat(np.arange(100, 108), 50))
        fs.decay(0.5)
    h_ids, _ = fs.hh.head()
    assert np.isin(np.arange(100, 108), h_ids).all()
    new_w = fs.estimate(np.arange(100, 108)).min()
    old_w = fs.estimate(np.arange(8)).max()
    assert new_w > old_w  # the histogram tracks the RECENT stream


# --- point sets (float counts, HT unbiasedness) -------------------------------


def test_float_counts_are_not_truncated():
    # decayed histogram summing to < 1: int() truncation used to turn
    # this into "nothing observed"
    counts = np.zeros(50)
    counts[[3, 30]] = [0.4, 0.3]
    s = sample_from_counts(counts, 100, seed=0)
    assert s is not None and set(np.unique(s)) <= {3, 30}
    ids, w = points_from_counts(counts, 10, seed=0)
    np.testing.assert_array_equal(ids, [3, 30])
    np.testing.assert_allclose(w, [0.4, 0.3], rtol=1e-6)
    assert sample_from_counts(np.zeros(4), 10, 0) is None
    assert points_from_counts(np.zeros(4), 10, 0) is None


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1.0, 0.9, 0.5, 0.25]))
def test_ht_tail_estimator_unbiased_under_decay(seed, gamma):
    """E_seed[total HT-subsampled weight] == the total decayed (float)
    mass — the stratified head + inverse-probability-inflated tail stays
    unbiased whatever the decay did to the counts."""
    rng = np.random.default_rng(seed)
    counts = rng.zipf(1.5, 400).astype(np.float64)
    counts *= gamma ** rng.integers(0, 12, 400)  # per-id decayed floats
    dense = np.zeros(4000)
    dense[rng.choice(4000, 400, replace=False)] = counts
    tots = [
        points_from_counts(dense, 40, seed=s)[1].sum()
        for s in range(60)
    ]
    np.testing.assert_allclose(np.mean(tots), dense.sum(), rtol=0.1)


def test_sketch_points_head_exact_tail_ht():
    ids = _zipf_stream(vocab=5000, n=40_000, seed=2)
    fs = FeatureSketch(width=1 << 11, depth=4, heavy=32, ring=4096, seed=0)
    for lo in range(0, ids.size, 4096):
        fs.observe(ids[lo : lo + 4096])
    true = np.bincount(ids, minlength=5000)
    pts, w = fs.points(64, seed=9)
    assert pts.size == 64 and np.unique(pts).size == 64
    # the n/2 head comes from the exact heavy-hitter counters
    top = np.argsort(true)[::-1][:16]
    assert np.isin(top, pts).all()
    lut = dict(zip(pts.tolist(), w.tolist()))
    for i in top.tolist():
        assert lut[i] == true[i]
    # deterministic by seed
    pts2, w2 = fs.points(64, seed=9)
    np.testing.assert_array_equal(pts, pts2)
    np.testing.assert_array_equal(w, w2)
    # under the cap: every head + ring candidate, no sampling
    few = FeatureSketch(width=1 << 8, depth=4, heavy=8, ring=64, seed=0)
    few.observe(np.asarray([5, 5, 9]))
    pts3, w3 = few.points(100, seed=0)
    np.testing.assert_array_equal(pts3, [5, 9])
    assert lut is not None and few.points(100, seed=1)[1][0] == 2.0


def test_sketch_id_weights_dense_view():
    fs = FeatureSketch(width=1 << 10, depth=4, heavy=16, ring=512, seed=0)
    fs.observe(np.repeat([3, 7, 11], [30, 20, 10]))
    w = fs.id_weights(100)
    assert w.shape == (100,) and w.dtype == np.float32
    assert w[3] == 30.0 and w[7] == 20.0 and w[11] == 10.0  # exact head


# --- tracker: memory, state, async --------------------------------------------


def test_tracker_memory_independent_of_vocab():
    """The acceptance criterion: O(width·depth + heavy + ring) state,
    asserted at a 10M-row config against a 1k-row config."""
    scfg = StreamConfig(width=1 << 12, depth=4, heavy=256, ring=4096)
    small = SketchFrequencyTracker((1000, 1000), scfg)
    big = SketchFrequencyTracker((10_000_000, 10_000_000), scfg)
    assert big.nbytes == small.nbytes
    per_feature = (
        scfg.width * scfg.depth * 8 + scfg.heavy * 16 + scfg.ring * 8
        + 2 * scfg.depth * 4  # hash coefficients
    )
    assert big.nbytes == 2 * per_feature
    # no state leaf scales with the vocabulary either
    assert all(leaf.size < 10_000_000 // 100 for leaf in big.state_tree())
    # ...and the full-Criteo factory config stays a few dozen MB
    tr = dlrm.make_id_tracker(dlrm_criteo.CONFIG, dlrm_criteo.STREAM)
    assert tr.nbytes < 64e6 < sum(dlrm_criteo.CONFIG.vocab_sizes) * 8


def test_tracker_state_roundtrip_and_windows():
    scfg = StreamConfig(width=1 << 9, depth=3, heavy=16, ring=128,
                        decay=0.5, window=2)
    tr = SketchFrequencyTracker((100, 200), scfg, tracked=(0, 1))
    rng = np.random.default_rng(0)
    for _ in range(4):
        tr.observe({"sparse": rng.integers(0, 100, (32, 2))})
    stats = tr.poll_window()
    assert stats is not None and stats["entropy"] > 0
    assert tr.poll_window() is None  # cleared on read
    tr2 = SketchFrequencyTracker((100, 200), scfg, tracked=(0, 1))
    tr2.load_state_tree(tr.state_tree())
    for a, b in zip(tr.state_tree(), tr2.state_tree()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tr2.batches_seen == 4


def test_async_fold_matches_sync_statistics():
    def mk(af):
        return SketchFrequencyTracker(
            (500, 9000), StreamConfig(width=1 << 10, depth=4, heavy=32,
                                      ring=512, async_fold=af), tracked=(0, 1),
        )

    sync, async_ = mk(False), mk(True)
    rng = np.random.default_rng(4)
    for _ in range(10):
        b = {"sparse": np.stack(
            [rng.zipf(1.3, 256) % 500, rng.zipf(1.3, 256) % 9000], axis=1
        )}
        sync.observe(b)
        async_.observe(b)
    async_.flush()
    for f in (0, 1):
        assert async_.features[f].mass == sync.features[f].mass
        # the two paths admit from different sketch estimates
        # (conservative vs plain), but the bulk of the head agrees
        hs = dict(zip(*[x.tolist() for x in sync.features[f].hh.head()]))
        ha = dict(zip(*[x.tolist() for x in async_.features[f].hh.head()]))
        assert len(set(hs) & set(ha)) >= len(hs) // 2
    # the providers the transition indexes ARE the feature sketches
    assert async_.counts[0] is async_.features[0]


# --- trigger policy -----------------------------------------------------------


def _stats(entropy, heads=None):
    return {"entropy": entropy, "mass": 1.0,
            "heads": heads if heads is not None else [None]}


def test_trigger_empty_and_single_id_never_fire():
    tg = ClusterTrigger(entropy_drop=0.1, warmup=0, min_windows_between=0)
    ev = tg.update(None, step=1)  # empty stream: nothing observed
    assert not ev.fire and np.isnan(ev.entropy)
    # single-id stream: entropy 0 from the first window — zero reference,
    # no collapse, never fires
    for s in range(2, 8):
        ev = tg.update(_stats(0.0), step=s)
        assert not ev.fire
    assert tg.fired == 0


def test_trigger_fires_exactly_once_per_collapse():
    tg = ClusterTrigger(entropy_drop=0.2, drift_threshold=2.0,  # drift off
                        warmup=1, min_windows_between=0)
    for s, h in enumerate([4.0, 4.1, 4.05]):  # healthy plateau
        assert not tg.update(_stats(h), step=s).fire
    ev = tg.update(_stats(3.0), step=3)  # collapse: 3.0 < 4.1 * 0.8
    assert ev.fire and ev.reason == "entropy-collapse"
    # stays low: NO re-fire (reference reset to the collapsed entropy)
    for s, h in enumerate([3.0, 2.9, 2.95], start=4):
        assert not tg.update(_stats(h), step=s).fire
    # a SECOND collapse from the new level fires again
    assert tg.update(_stats(2.2), step=8).fire
    assert tg.fired == 2


def test_trigger_fires_on_drift():
    heads_a = [(np.arange(8), np.full(8, 0.125))]
    heads_b = [(np.arange(100, 108), np.full(8, 0.125))]  # disjoint head
    tg = ClusterTrigger(entropy_drop=0.99, drift_threshold=0.5,
                        warmup=1, min_windows_between=0)
    assert not tg.update(_stats(3.0, heads_a), step=1).fire
    assert not tg.update(_stats(3.0, heads_a), step=2).fire  # no drift
    ev = tg.update(_stats(3.0, heads_b), step=3)
    assert ev.fire and ev.reason == "drift" and ev.drift == pytest.approx(1.0)


def test_trigger_state_roundtrip_is_exact():
    tg = ClusterTrigger(entropy_drop=0.2, warmup=1, min_windows_between=0)
    heads = [(np.arange(4), np.asarray([0.4, 0.3, 0.2, 0.1]))]
    seq = [4.0, 4.2, 3.1, 3.0, 2.2, 2.25]
    mid = len(seq) // 2
    for s, h in enumerate(seq[:mid]):
        tg.update(_stats(h, heads), step=s)
    tg2 = ClusterTrigger(entropy_drop=0.2, warmup=1, min_windows_between=0)
    tg2.load_state_tree(tg.state_tree())
    fires = []
    for s, h in enumerate(seq[mid:], start=mid):
        fires.append(
            (tg.update(_stats(h, heads), step=s).fire,
             tg2.update(_stats(h, heads), step=s).fire)
        )
    assert all(a == b for a, b in fires) and any(a for a, _ in fires)
    assert tg.fired == tg2.fired


# --- Trainer integration ------------------------------------------------------


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


def test_make_id_tracker_tracks_only_cce_features():
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    tr = dlrm.make_id_tracker(cfg, dlrm_criteo.reduced_stream())
    from repro.core.cce import CCE

    cce_feats = {
        i for i, t in enumerate(cfg.collection.tables) if isinstance(t, CCE)
    }
    assert set(tr.tracked) == cce_feats
    for i in range(cfg.n_sparse):
        assert (tr.counts[i] is None) == (i not in cce_feats)
    assert isinstance(dlrm.make_id_tracker(cfg), IdFrequencyTracker)


def test_transition_receives_sketch_points(monkeypatch):
    """With a sketch provider the transition must hand cluster() the
    exact head ids/counts (plus HT tail) — not a dense array."""
    from repro.core.cce import CCE
    from repro.train.transition import transition_table

    cce = CCE(d1=3000, d2=16, k=8, c=4, seed_salt=3)
    params, buffers = cce.init(jax.random.PRNGKey(0))
    fs = FeatureSketch(width=1 << 10, depth=4, heavy=16, ring=256, seed=0)
    fs.observe(np.repeat([7, 13, 99], [5, 1, 2]))
    seen = {}
    orig = CCE.cluster

    def spy(self, key, p, b, **kw):
        seen.update(kw)
        return orig(self, key, p, b, **kw)

    monkeypatch.setattr(CCE, "cluster", spy)
    transition_table(cce, jax.random.PRNGKey(0), params, buffers, counts=fs)
    np.testing.assert_array_equal(np.asarray(seen["sample_ids"]), [7, 13, 99])
    np.testing.assert_array_equal(np.asarray(seen["sample_weights"]), [5.0, 1.0, 2.0])


def test_trainer_trigger_fires_transition_and_training_continues():
    cfg, step, state, static, data = _setup()
    tracker = dlrm.make_id_tracker(cfg, dlrm_criteo.reduced_stream(window=5))
    trigger = ClusterTrigger(entropy_drop=0.05, drift_threshold=0.05, warmup=1)

    def cluster_fn(key, p, b, opt):
        return dlrm.cluster_tables(key, p, b, cfg, opt, id_counts=tracker.counts)

    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 cluster_fn=cluster_fn, cluster_every=0, cluster_max=2,
                 id_tracker=tracker, trigger=trigger)
    hist = tr.run(25)
    assert tr.clusters_done == 2  # adaptive schedule fired (capped)
    assert trigger.fired >= 2 and len(trigger.events) == 5
    assert np.isfinite(hist[-1]["loss"])


def test_restart_exact_with_sketch_tracker_and_trigger(tmp_path):
    """Crash after a TRIGGERED transition, restore, replay: bitwise-equal
    final state — the sketch histograms, the trigger's reference/latch,
    and the fired schedule are all training state."""

    def make(cfg, tracker, trigger):
        def cluster_fn(key, p, b, opt):
            return dlrm.cluster_tables(key, p, b, cfg, opt,
                                       id_counts=tracker.counts)

        return dict(cluster_fn=cluster_fn, cluster_every=0, cluster_max=3,
                    id_tracker=tracker, trigger=trigger, seed=1)

    def mk_parts():
        cfg, step, state, static, data = _setup(seed=1)
        tracker = dlrm.make_id_tracker(
            cfg, dlrm_criteo.reduced_stream(window=3))
        trigger = ClusterTrigger(entropy_drop=0.05, drift_threshold=0.05,
                                 warmup=1)
        return cfg, step, state, static, data, tracker, trigger

    def run(fail: bool):
        cfg, step, state, static, data, tracker, trigger = mk_parts()
        tr = Trainer(
            jax.jit(step, donate_argnums=(0,)), state, static, data,
            ckpt_dir=str(tmp_path / ("a" if fail else "b")), ckpt_every=5,
            failures=FailureInjector((8,)) if fail else None,
            **make(cfg, tracker, trigger),
        )
        if fail:
            with pytest.raises(RuntimeError):
                tr.run(12)
            cfg2, step2, _, static2, _, tracker2, trigger2 = mk_parts()
            tr2 = Trainer(
                jax.jit(step2, donate_argnums=(0,)), tr.state, static2,
                clickstream_batches(
                    ClickstreamConfig(vocab_sizes=cfg2.vocab_sizes, seed=1),
                    32, start_step=5,
                ),
                ckpt_dir=str(tmp_path / "a"), **make(cfg2, tracker2, trigger2),
            )
            restored = tr2.restore_latest()
            assert restored == 5
            assert tracker2.batches_seen == 5  # sketch state resumed
            tr2.run(12 - restored)
            return tr2.state, trigger2
        tr.run(12)
        return tr.state, trigger

    (s_fail, tg_fail), (s_clean, tg_clean) = run(True), run(False)
    assert tg_fail.fired == tg_clean.fired  # the schedule replayed
    for a, b in zip(jax.tree.leaves(s_fail.params), jax.tree.leaves(s_clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_fail.opt), jax.tree.leaves(s_clean.opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_checkpoint_migrates_into_sketch_tracker(tmp_path):
    """Satellite: a checkpoint written by a DENSE-tracker Trainer restores
    into a sketch-tracker Trainer through load_checkpoint(migrations=...)
    — head ids carry their exact (bit-for-bit) dense counts."""
    cfg, step, state, static, data = _setup(seed=2)
    dense = IdFrequencyTracker(cfg.vocab_sizes)
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 ckpt_dir=str(tmp_path), ckpt_every=4, id_tracker=dense)
    tr.run(4)
    tr.ckpt.wait()
    dense_counts = [c.copy() for c in dense.counts]

    cfg2, step2, state2, static2, _ = _setup(seed=2)
    sketch = dlrm.make_id_tracker(cfg2, dlrm_criteo.reduced_stream(window=0))
    tr2 = Trainer(jax.jit(step2, donate_argnums=(0,)), state2, static2,
                  iter(()), ckpt_dir=str(tmp_path), id_tracker=sketch)
    assert tr2.restore_latest() == 4
    heavy = sketch.config.heavy
    for f in sketch.tracked:
        c = dense_counts[f]
        nz = np.flatnonzero(c)
        top = nz[np.argsort(c[nz], kind="stable")[::-1]][:heavy]
        h_ids, h_cnt = sketch.features[f].hh.head()
        lut = dict(zip(h_ids.tolist(), h_cnt.tolist()))
        for i in top.tolist():
            assert lut[i] == float(c[i])  # bit-for-bit on the head
        assert sketch.features[f].mass == float(c.sum())
        # the sketch never underestimates the remaining tail
        tail = np.setdiff1d(nz, top)
        if tail.size:
            assert (sketch.features[f].cms.estimate(tail) >= c[tail]).all()


def test_sketch_checkpoint_roundtrip_via_trainer(tmp_path):
    """Sketch-tracker checkpoints restore exactly (sectioned manifest) —
    including when the reader adds a trigger the writer didn't have."""
    cfg, step, state, static, data = _setup(seed=3)
    tracker = dlrm.make_id_tracker(cfg, dlrm_criteo.reduced_stream(window=2))
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 ckpt_dir=str(tmp_path), ckpt_every=4, id_tracker=tracker)
    tr.run(4)
    tr.ckpt.wait()
    want = [np.asarray(leaf) for leaf in tracker.state_tree()]

    cfg2, step2, state2, static2, _ = _setup(seed=3)
    tracker2 = dlrm.make_id_tracker(cfg2, dlrm_criteo.reduced_stream(window=2))
    trigger2 = ClusterTrigger()
    tr2 = Trainer(jax.jit(step2, donate_argnums=(0,)), state2, static2,
                  iter(()), ckpt_dir=str(tmp_path), id_tracker=tracker2,
                  trigger=trigger2)
    assert tr2.restore_latest() == 4
    for a, b in zip(want, tracker2.state_tree()):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert trigger2.windows == 0  # fresh trigger state, not garbage


def test_combined_legacy_emb_and_dense_counts_checkpoint_migrates(tmp_path):
    """Migrations COMPOSE: a pre-collection-era checkpoint (per-feature
    emb layout, dense id_counts, no section index in the manifest) must
    restore into a grouped-layout Trainer with a SKETCH tracker — old
    along both axes at once."""
    import json
    import os

    from repro.checkpoint import save_checkpoint
    from repro.core.collection import legacy_layout_migration

    cfg, step, state, static, data = _setup(seed=4)
    dense = IdFrequencyTracker(cfg.vocab_sizes)
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 id_tracker=dense)
    tr.run(3)
    emb_to_old, _ = legacy_layout_migration(cfg.collection)
    legacy_tree = emb_to_old(tr._ckpt_tree())
    path = save_checkpoint(str(tmp_path), 3, legacy_tree)
    manifest = os.path.join(path, "manifest.json")
    with open(manifest) as f:
        m = json.load(f)
    del m["toplevel"]  # pre-PR4 writers had no section index
    with open(manifest, "w") as f:
        json.dump(m, f)

    cfg2, step2, state2, static2, _ = _setup(seed=4)
    sketch = dlrm.make_id_tracker(cfg2, dlrm_criteo.reduced_stream(window=0))
    tr2 = Trainer(jax.jit(step2, donate_argnums=(0,)), state2, static2,
                  iter(()), ckpt_dir=str(tmp_path), id_tracker=sketch,
                  migrations=dlrm.checkpoint_migrations(cfg2))
    assert tr2.restore_latest() == 3
    # params restored bit-exact through the re-stacking migration
    for a, b in zip(jax.tree.leaves(tr.state.params),
                    jax.tree.leaves(tr2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # histograms ingested: exact head counts per tracked feature
    f = sketch.tracked[0]
    c = dense.counts[f]
    top = np.argsort(c)[::-1][: min(8, int((c > 0).sum()))]
    lut = dict(zip(*[x.tolist() for x in sketch.features[f].hh.head()]))
    for i in top.tolist():
        assert lut[i] == float(c[i])


def test_trigger_restores_from_pre_first_window_checkpoint(tmp_path):
    """The checkpoint template must accept trigger state saved BEFORE the
    first closed window (empty prev-head snapshot) even when the LIVE
    trigger has closed windows since — in-process crash recovery must
    restore the stored state, not silently keep the stale live state."""
    cfg, step, state, static, data = _setup(seed=5)
    tracker = dlrm.make_id_tracker(cfg, dlrm_criteo.reduced_stream(window=8))
    trigger = ClusterTrigger(entropy_drop=0.05, drift_threshold=0.05, warmup=0)
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 ckpt_dir=str(tmp_path), ckpt_every=5,
                 failures=FailureInjector((9,)),
                 id_tracker=tracker, trigger=trigger, seed=5)
    with pytest.raises(RuntimeError):
        tr.run(12)  # ckpt at 5 (no window closed yet), window at 8, crash at 9
    assert trigger.windows == 1  # the live trigger HAS closed a window
    assert tr.restore_latest() == 5
    # stored pre-window state restored: reference re-armed, events dropped
    assert trigger.windows == 0 and trigger._prev_ids is None
    assert trigger.events == []


def test_trackerless_writer_restores_fresh_tracker_state(tmp_path):
    """A sectioned checkpoint from a tracker-less writer restored into a
    tracker-enabled Trainer must reset the tracker to DETERMINISTIC fresh
    state — not silently keep the live tracker's post-checkpoint
    observations (in-process crash recovery would diverge)."""
    cfg, step, state, static, data = _setup(seed=6)
    tr = Trainer(jax.jit(step, donate_argnums=(0,)), state, static, data,
                 ckpt_dir=str(tmp_path), ckpt_every=3)  # NO tracker
    tr.run(3)
    tr.ckpt.wait()

    cfg2, step2, state2, static2, data2 = _setup(seed=6)
    sketch = dlrm.make_id_tracker(cfg2, dlrm_criteo.reduced_stream(window=0))
    tr2 = Trainer(jax.jit(step2, donate_argnums=(0,)), state2, static2,
                  data2, ckpt_dir=str(tmp_path), id_tracker=sketch)
    tr2.run(2)  # live tracker accumulates PRE-restore observations
    assert sketch.features[sketch.tracked[0]].mass > 0
    assert tr2.restore_latest() == 3
    for f in sketch.tracked:
        assert sketch.features[f].mass == 0.0  # fresh, not stale live state
    assert sketch.batches_seen == 0
    # dense reader: same fresh semantics
    cfg3, step3, state3, static3, data3 = _setup(seed=6)
    dense = dlrm.make_id_tracker(cfg3)
    tr3 = Trainer(jax.jit(step3, donate_argnums=(0,)), state3, static3,
                  data3, ckpt_dir=str(tmp_path), id_tracker=dense)
    tr3.run(2)
    assert tr3.restore_latest() == 3
    assert all(int(c.sum()) == 0 for c in dense.counts)


def test_trigger_survives_tracked_feature_count_change():
    """A restored prev-head snapshot with a different feature count (the
    wildcard restore template accepts any stored row count) must reset
    the drift baseline, not crash or pair mismatched features."""
    tg = ClusterTrigger(entropy_drop=0.99, drift_threshold=0.5,
                        warmup=0, min_windows_between=0)
    one = [(np.arange(4), np.full(4, 0.25))]
    two = one + [(np.arange(10, 14), np.full(4, 0.25))]
    tg.update(_stats(3.0, one), step=1)
    ev = tg.update(_stats(3.0, two), step=2)  # feature count 1 -> 2
    assert not ev.fire and ev.drift == 0.0  # baseline reset, no IndexError
    ev = tg.update(_stats(3.0, two), step=3)
    assert ev.drift == pytest.approx(0.0)  # baseline re-established


# --- multi-hot bags ---------------------------------------------------------


def _bag_batch(vocab, bags, B, rng):
    return np.concatenate([rng.zipf(1.2, (B, n)) % v for v, n in zip(vocab, bags)],
                          axis=1).astype(np.int32)


def test_in_step_counter_counts_every_id_of_a_bag():
    """The step's cell delta of a tracked feature is the bincount of ALL
    its bag columns' ids, hashed with that feature's rows."""
    from repro.stream import make_step_cell_counter

    vocab, bags = (50, 3000, 70_000, 9), (3, 1, 4, 2)
    trk = SketchFrequencyTracker(vocab, StreamConfig(width=1 << 8, depth=3),
                                 tracked=(0, 2, 3), bag_sizes=bags)
    sparse = _bag_batch(vocab, bags, 64, np.random.default_rng(4))
    got = np.asarray(jax.jit(make_step_cell_counter(trk))({"sparse": jnp.asarray(sparse)}))
    cols = np.cumsum((0,) + bags)
    for j, f in enumerate(trk.tracked):
        cms = trk.features[f].cms
        cells = cms.cells(sparse[:, cols[f]:cols[f + 1]].ravel())
        want = np.stack([np.bincount(cells[r], minlength=cms.width) for r in range(3)])
        np.testing.assert_array_equal(got[j], want)


@pytest.mark.parametrize("async_fold", [False, True])
def test_bag_fold_offers_every_id_of_a_bag(async_fold):
    """With bags, a tracked feature's sketch, head and mass take every id
    of its bag: the same state as a single-id tracker of that feature (the
    same hash rows) fed the feature's bag ids flattened, example by
    example, each step's delta counted in the step."""
    from repro.stream import make_step_cell_counter

    vocab, bags = (40, 3000, 9, 500_000), (3, 1, 2, 5)
    scfg = StreamConfig(width=1 << 9, depth=3, heavy=32, ring=64, decay=0.9,
                        window=4, async_fold=async_fold)
    trk = SketchFrequencyTracker(vocab, scfg, tracked=(0, 1, 3), bag_sizes=bags)
    flat = {f: SketchFrequencyTracker(vocab[: f + 1], scfg, tracked=(f,)) for f in trk.tracked}
    rng = np.random.default_rng(5)
    cols = np.cumsum((0,) + bags)
    counters = {id(t): jax.jit(make_step_cell_counter(t)) for t in [trk, *flat.values()]}

    def feed(t, sparse):
        t.observe({"sparse": sparse}, delta=counters[id(t)]({"sparse": jnp.asarray(sparse)}))

    for _ in range(9):
        sparse = _bag_batch(vocab, bags, 32, rng)
        feed(trk, sparse)
        for f, fl in flat.items():
            one = np.zeros((32 * bags[f], f + 1), np.int32)
            one[:, f] = sparse[:, cols[f]:cols[f + 1]].ravel()
            feed(fl, one)
    trk.flush()
    for f, fl in flat.items():
        fl.flush()
        a, b = trk.features[f], fl.features[f]
        assert a.mass == b.mass > 0
        for x, y in zip(a.state_tree(), b.state_tree()):
            np.testing.assert_array_equal(x, y)
