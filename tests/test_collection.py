"""EmbeddingCollection: grouped supertables == the per-table loop.

The refactor's contract, asserted here:
  * universal fusion drops heavy lookups from O(n_features) to ONE launch
    on a compressed config (``n_lookup_launches`` AND a jaxpr-level
    pallas_call count, so a refactor can't silently reintroduce the
    per-feature loop),
  * the fused path (Pallas kernel AND jnp oracle) is numerically
    equivalent to the legacy per-feature loop — forward and gradients —
    for every fusable method (CCE, hash, CE-concat, small full tables),
  * ragged codebooks (different k in one group), mixed methods in one
    supertable, and the padded full-table gather are exact,
  * host-side pointer translation (``data.translate``) is BIT-exact with
    the device row path and leaves the pointer buffers untouched,
  * pre-collection (per-feature layout) checkpoints restore BIT-EXACT
    through ``Trainer.restore_latest`` + ``dlrm.checkpoint_migrations``,
  * the collection-backed transition keeps the Trainer protocol intact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dlrm_criteo
from repro.core.cce import CCE
from repro.core.collection import EmbeddingCollection
from repro.core.embeddings import CEConcat, FullTable, HashingTrick
from repro.models import dlrm
from repro.models.dlrm import DLRMConfig
from repro.optim import sgd


MIXED = DLRMConfig(
    vocab_sizes=(8, 1000, 20, 5000, 16, 300),
    n_dense=13, emb_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
    emb_method="cce", emb_param_cap=512,
)


def _batch(cfg, B=9, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "dense": jnp.asarray(rng.normal(size=(B, cfg.n_dense)), jnp.float32),
        "sparse": jnp.asarray(
            np.stack([rng.integers(0, v, B) for v in cfg.vocab_sizes], axis=1),
            jnp.int32,
        ),
        "label": jnp.asarray(rng.integers(0, 2, B), jnp.float32),
    }


def _per_feature_lookup(coll, emb_params, emb_buffers, sparse):
    """The legacy hot loop: one lookup per feature."""
    per_p = coll.unstack_params(emb_params)
    per_b = coll.unstack_buffers(emb_buffers)
    return jnp.stack(
        [
            coll.tables[i].lookup(per_p[i], per_b[i], sparse[:, i])
            for i in range(coll.n_features)
        ],
        axis=1,
    )


# --- grouping ------------------------------------------------------------


def test_grouping_collapses_launches():
    # all-compressed reduced config: every table fuses into ONE launch
    coll = dlrm_criteo.reduced(emb_method="cce", cap=512).collection
    assert coll.n_features == 5 and coll.n_groups == 1
    assert coll.n_lookup_launches == 1
    assert coll.groups[0].kind == "univ"
    # mixed cce/full config: the small full tables JOIN the supertable
    # (identity rows, T-sentinel padding) — still ONE launch
    coll = MIXED.collection
    assert [g.kind for g in coll.groups] == ["univ"]
    assert coll.n_lookup_launches == 1
    # every feature appears in exactly one group
    feats = sorted(i for g in coll.groups for i in g.features)
    assert feats == list(range(coll.n_features))


def test_criteo_config_is_one_launch():
    """The acceptance criterion: the full Criteo DLRM config (capped
    CCE + small full tables) issues ONE heavy embedding launch."""
    coll = dlrm_criteo.CONFIG.collection
    assert coll.n_features == 26
    assert coll.n_lookup_launches == 1
    assert [g.kind for g in coll.groups] == ["univ"]


def test_hash_and_ce_groups_fuse():
    """The QREmbeddingBag lesson applies to the hashed methods too: one
    launch, not a per-feature loop (the PR-3 fallback)."""
    for method in ("hash", "ce"):
        coll = dlrm_criteo.reduced(emb_method=method, cap=512).collection
        assert coll.n_lookup_launches == 1, method
        assert [g.kind for g in coll.groups] == ["univ"], method


def test_full_groups_split_on_pathological_padding():
    """A (tiny, huge) full-table mix must NOT pad the tiny table to the
    huge vocab (full-only buckets keep the padded batched gather — a
    one-hot matmul over d1 rows has nothing to amortize against)."""
    tables = tuple(FullTable(d1, 16) for d1 in (8, 16, 100_000))
    coll = EmbeddingCollection.build(tables)
    full_groups = [g for g in coll.groups if g.kind == "full"]
    assert len(full_groups) == 2  # {8, 16} together, 100k alone
    assert not [g for g in coll.groups if g.kind == "univ"]
    sizes = sorted(tuple(t.d1 for t in g.tables) for g in full_groups)
    assert sizes == [(8, 16), (100_000,)]


def test_big_full_tables_stay_out_of_the_supertable():
    """A full table whose d1 dwarfs the compressed codebooks must not
    join the one-hot supertable (k_pad would explode); it keeps the
    gather path."""
    tables = (CCE(d1=10_000, d2=16, k=16, c=4), FullTable(100_000, 16))
    coll = EmbeddingCollection.build(tables)
    assert sorted(g.kind for g in coll.groups) == ["full", "univ"]
    assert coll.n_lookup_launches == 2


def test_univ_groups_split_on_k_spread():
    """One huge-k member must not inflate every other member's codebook
    axis (params, moments and one-hot work all scale with k_pad): the
    waste bound splits the bucket instead."""
    tables = (
        CCE(d1=10_000, d2=16, k=16, c=4, seed_salt=0),
        HashingTrick(d1=500_000, d2=16, k=100_000, seed_salt=1),
    )
    coll = EmbeddingCollection.build(tables)
    assert [g.kind for g in coll.groups] == ["univ", "univ"]
    assert coll.n_lookup_launches == 2
    # the CCE slab keeps its natural codebook size, not the hash table's
    params, _ = coll.init(jax.random.PRNGKey(0))
    g_cce = coll._locate[0][0]
    assert params[g_cce]["tables"].shape[2] == 16


def test_univ_waste_bound_is_per_member_too():
    """A dominant huge-k member must not carry a tiny member to
    megabytes of dead padding even when the AGGREGATE ratio looks fine
    (the 8-row table would be padded to a 100k-row codebook while
    barely moving the bucket total)."""
    tables = (
        HashingTrick(d1=500_000, d2=16, k=100_000, seed_salt=0),
        FullTable(8, 16),
    )
    coll = EmbeddingCollection.build(tables)
    # the tiny full table splits off; alone it reverts to the gather
    assert sorted(g.kind for g in coll.groups) == ["full", "univ"]
    # ...while Criteo's tiny full tables still fuse (absolute slack:
    # kilobytes of padding buys the single launch)
    assert dlrm_criteo.CONFIG.collection.n_lookup_launches == 1


def test_loop_fallback_for_unfusable_methods():
    coll = dlrm_criteo.reduced(emb_method="robe", cap=512).collection
    assert all(g.kind == "loop" for g in coll.groups)
    assert coll.n_lookup_launches == coll.n_features


def test_collection_modes_are_benchmark_baselines():
    """mode="group"/"loop" reproduce the pre-universal groupings (for
    bench_kernels --fuse) and agree numerically with the default."""
    coll = MIXED.collection
    key = jax.random.PRNGKey(3)
    p1, b1 = coll.init(key)
    sparse = _batch(MIXED, B=19, seed=5)["sparse"]
    want = coll.lookup_all(p1, b1, sparse, use_kernel=False)
    legacy = EmbeddingCollection.build(coll.tables, mode="group")
    assert sorted(g.kind for g in legacy.groups) == ["full", "univ"]
    loop = EmbeddingCollection.build(coll.tables, mode="loop")
    assert loop.n_lookup_launches == loop.n_features
    for c2 in (legacy, loop):
        p2, b2 = c2.init(key)
        got = c2.lookup_all(p2, b2, sparse, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cached_collection_is_not_reconstructed():
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    assert cfg.collection is cfg.collection  # cached_property, one build
    assert cfg.table(0) is cfg.collection.tables[0]


# --- numerics: fused == looped --------------------------------------------


@pytest.mark.parametrize("use_kernel", [True, False])
def test_lookup_all_matches_per_feature_loop(use_kernel):
    cfg = MIXED
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    sparse = _batch(cfg, B=33)["sparse"]  # B not a block multiple
    got = coll.lookup_all(
        params["emb"], buffers["emb"], sparse, use_kernel=use_kernel
    )
    want = _per_feature_lookup(coll, params["emb"], buffers["emb"], sparse)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("use_kernel", [True, False])
def test_lookup_all_grads_match_per_feature_loop(use_kernel):
    cfg = MIXED
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(1), cfg)
    sparse = _batch(cfg, B=17, seed=1)["sparse"]
    co = jax.random.normal(jax.random.PRNGKey(2), (17, cfg.n_sparse, cfg.emb_dim))

    def loss_fused(emb_p):
        out = coll.lookup_all(emb_p, buffers["emb"], sparse, use_kernel=use_kernel)
        return jnp.sum(out * co)

    def loss_looped(emb_p):
        out = _per_feature_lookup(coll, emb_p, buffers["emb"], sparse)
        return jnp.sum(out * co)

    g1 = jax.grad(loss_fused)(params["emb"])
    g2 = jax.grad(loss_looped)(params["emb"])
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_dlrm_forward_kernel_path_matches_jnp_path():
    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, B=21)
    out_k = dlrm.forward(params, buffers, cfg, batch)
    cfg_j = dataclasses.replace(cfg, emb_use_kernel=False)
    out_j = dlrm.forward(params, buffers, cfg_j, batch)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_j), rtol=1e-5, atol=1e-5
    )
    g_k = jax.grad(lambda p: dlrm.bce_loss(p, buffers, cfg, batch))(params)
    g_j = jax.grad(lambda p: dlrm.bce_loss(p, buffers, cfg_j, batch))(params)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_j)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_ragged_codebooks_fuse_exactly():
    """Two CCE tables with DIFFERENT k in one group: the supertable pads
    the codebook axis and lookups stay exact, grads land only in real rows."""
    t1 = CCE(d1=100, d2=16, k=5, c=4, seed_salt=0)
    t2 = CCE(d1=200, d2=16, k=12, c=4, seed_salt=1)
    coll = EmbeddingCollection.build((t1, t2))
    assert coll.n_groups == 1 and coll.groups[0].kind == "univ"
    params, buffers = coll.init(jax.random.PRNGKey(0))
    assert params[0]["tables"].shape == (8, 2, 12, 4)  # padded to max k
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 100, (13, 2)), jnp.int32)
    got = coll.lookup_all(params, buffers, ids, use_kernel=True)
    want = _per_feature_lookup(coll, params, buffers, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    # gradient never touches the padding rows of the small-k table
    g = jax.grad(
        lambda p: jnp.sum(coll.lookup_all(p, buffers, ids, use_kernel=True) ** 2)
    )(params)
    assert float(np.abs(np.asarray(g[0]["tables"][:4, :, 5:, :])).max()) == 0.0


def test_full_group_clamps_out_of_range_ids_like_per_table():
    """An id >= a small table's vocab must clamp to ITS last row (the
    per-table XLA gather semantics), not read — or train — the padding
    rows of the stacked (F, max d1, d2) table."""
    tables = (FullTable(4, 8), FullTable(16, 8))
    coll = EmbeddingCollection.build(tables)
    params, buffers = coll.init(jax.random.PRNGKey(0))
    ids = jnp.asarray([[4, 0], [99, 15]], jnp.int32)  # 4, 99 out of range for d1=4
    got = coll.lookup_all(params, buffers, ids)
    want = _per_feature_lookup(coll, params, buffers, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # gradient lands in the clamped real row, never in the padding
    g = jax.grad(lambda p: jnp.sum(coll.lookup_all(p, buffers, ids) ** 2))(params)
    assert float(np.abs(np.asarray(g[0]["table"][0, 4:])).max()) == 0.0


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("method", ["hash", "ce"])
def test_fused_hash_ce_matches_loop_fallback(method, use_kernel):
    """Fused hash/CEConcat groups vs the per-feature loop: forward AND
    gradient, ragged k within the group, B not a block multiple."""
    if method == "hash":
        tables = (
            HashingTrick(d1=1000, d2=16, k=24, seed_salt=0),
            HashingTrick(d1=5000, d2=16, k=64, seed_salt=1),  # ragged k
            HashingTrick(d1=77, d2=16, k=8, seed_salt=2),
        )
    else:
        tables = (
            CEConcat(d1=1000, d2=16, k=24, c=4, seed_salt=0),
            CEConcat(d1=5000, d2=16, k=64, c=4, seed_salt=1),
            CEConcat(d1=77, d2=16, k=8, c=4, seed_salt=2),
        )
    coll = EmbeddingCollection.build(tables)
    assert coll.n_lookup_launches == 1 and coll.groups[0].kind == "univ"
    params, buffers = coll.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B = 33  # not a multiple of b_blk
    ids = jnp.asarray(
        np.stack([rng.integers(0, t.d1, B) for t in tables], axis=1), jnp.int32
    )
    got = coll.lookup_all(params, buffers, ids, use_kernel=use_kernel)
    want = _per_feature_lookup(coll, params, buffers, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )
    co = jax.random.normal(jax.random.PRNGKey(1), got.shape)
    g1 = jax.grad(
        lambda p: jnp.sum(
            coll.lookup_all(p, buffers, ids, use_kernel=use_kernel) * co
        )
    )(params)
    g2 = jax.grad(
        lambda p: jnp.sum(_per_feature_lookup(coll, p, buffers, ids) * co)
    )(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("use_kernel", [True, False])
def test_mixed_method_supertable_matches_loop(use_kernel):
    """CCE + hash + CE + full tables in ONE supertable launch: sub-column
    splitting (hash dsub 16 -> group gcd 4) and sentinel T-padding
    compose, forward and gradient."""
    tables = (
        CCE(d1=2000, d2=16, k=16, c=4, seed_salt=0),
        HashingTrick(d1=900, d2=16, k=32, seed_salt=1),
        CEConcat(d1=700, d2=16, k=12, c=4, seed_salt=2),
        FullTable(40, 16),
    )
    coll = EmbeddingCollection.build(tables)
    assert coll.n_lookup_launches == 1
    grp = coll.groups[0]
    assert grp.kind == "univ" and grp.dsub == 4 and grp.n_tables == 2
    assert grp.col_counts == (4, 4, 4, 4)  # hash/full split 16 -> 4x4
    params, buffers = coll.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    ids = jnp.asarray(
        np.stack([rng.integers(0, t.d1, 21) for t in tables], axis=1), jnp.int32
    )
    got = coll.lookup_all(params, buffers, ids, use_kernel=use_kernel)
    want = _per_feature_lookup(coll, params, buffers, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )
    co = jax.random.normal(jax.random.PRNGKey(3), got.shape)
    g1 = jax.grad(
        lambda p: jnp.sum(
            coll.lookup_all(p, buffers, ids, use_kernel=use_kernel) * co
        )
    )(params)
    g2 = jax.grad(
        lambda p: jnp.sum(_per_feature_lookup(coll, p, buffers, ids) * co)
    )(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )
    # the single-sub-table members' sentinel T slots get EXACTLY zero
    # gradient (they must stay zero so stacking stays value-preserving)
    slab_g = g1[0]["tables"]  # (16, 2, k_pad, 4)
    assert float(jnp.abs(slab_g[4:, 1]).max()) == 0.0  # hash/ce/full helpers


# --- launch counting at the jaxpr level ------------------------------------


def test_jaxpr_launch_count_matches_n_lookup_launches():
    """The regression guard behind ``n_lookup_launches``: the lowered
    program really contains exactly ONE pallas launch for the forward
    (and one more for the backward scatter-add)."""
    cfg = MIXED
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    sparse = _batch(cfg, B=16)["sparse"]

    from repro.analysis import count_primitive

    fwd = jax.make_jaxpr(
        lambda p: coll.lookup_all(p, buffers["emb"], sparse, use_kernel=True)
    )(params["emb"])
    assert count_primitive(fwd, "pallas_call") == coll.n_lookup_launches == 1

    grad = jax.make_jaxpr(
        jax.grad(
            lambda p: jnp.sum(
                coll.lookup_all(p, buffers["emb"], sparse, use_kernel=True)
            )
        )
    )(params["emb"])
    assert count_primitive(grad, "pallas_call") == 2  # fwd + bwd, nothing else

    # whole-model check: the full DLRM loss step still lowers to exactly
    # one forward launch
    batch = _batch(cfg, B=16)
    cfg_k = dataclasses.replace(cfg, emb_use_kernel=True)
    loss_jaxpr = jax.make_jaxpr(
        lambda p: dlrm.bce_loss(p, buffers, cfg_k, batch)
    )(params)
    assert count_primitive(loss_jaxpr, "pallas_call") == 1


# --- host-side pointer translation (DESIGN.md §4/§6) -----------------------


def test_host_translated_rows_match_device_bitexact():
    from repro.data import HostTranslator

    cfg = MIXED
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(4)
    sparse = np.stack(
        [rng.integers(0, v, 33) for v in cfg.vocab_sizes], axis=1
    ).astype(np.int32)
    tr = HostTranslator(coll, buffers["emb"])
    rows = tr.rows(sparse)
    assert rows.shape == (33, coll.rows_n_cols, coll.rows_n_tables)
    # host rows == device rows, bit for bit
    (dev,) = coll.group_rows(coll.groups[0], buffers["emb"][0], jnp.asarray(sparse))
    np.testing.assert_array_equal(np.moveaxis(rows, 0, 1), np.asarray(dev))
    # lookup through host rows == device-translated lookup, bit for bit
    for uk in (True, False):
        a = coll.lookup_all(
            params["emb"], buffers["emb"], jnp.asarray(sparse), use_kernel=uk
        )
        b = coll.lookup_all(
            params["emb"], buffers["emb"], None, use_kernel=uk,
            rows=jnp.asarray(rows),
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_translation_clamps_out_of_range_ids_like_device():
    """Dirty ids must not crash (or diverge from) the host translator:
    the jitted device gather clamps, so the numpy twin clamps too —
    bit-exact rows either way."""
    from repro.data import HostTranslator

    cfg = MIXED
    coll = cfg.collection
    _, buffers = dlrm.init(jax.random.PRNGKey(9), cfg)
    tr = HostTranslator(coll, buffers["emb"])
    # ids at and past every feature's vocab edge
    sparse = np.stack(
        [np.array([0, v - 1, v, v + 99]) for v in cfg.vocab_sizes], axis=1
    ).astype(np.int32)
    rows = tr.rows(sparse)
    (dev,) = jax.jit(
        lambda ids: coll.group_rows(coll.groups[0], buffers["emb"][0], ids)
    )(jnp.asarray(sparse))
    np.testing.assert_array_equal(np.moveaxis(rows, 0, 1), np.asarray(dev))


def test_host_translation_tracks_transitions():
    """The mirrors are snapshots: after a clustering transition rewrites
    ptr/hs, ``update`` re-syncs and parity holds again."""
    from repro.data import HostTranslator
    from repro.train.transition import transition_collection

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(5), cfg)
    tr = HostTranslator(coll, buffers["emb"])
    new_p, new_b, _ = transition_collection(
        coll, jax.random.PRNGKey(6), params["emb"], buffers["emb"]
    )
    tr.update(new_b)
    rng = np.random.default_rng(6)
    sparse = np.stack(
        [rng.integers(0, v, 17) for v in cfg.vocab_sizes], axis=1
    ).astype(np.int32)
    rows = tr.rows(sparse)
    (dev,) = coll.group_rows(coll.groups[0], new_b[0], jnp.asarray(sparse))
    np.testing.assert_array_equal(np.moveaxis(rows, 0, 1), np.asarray(dev))
    a = coll.lookup_all(new_p, new_b, jnp.asarray(sparse), use_kernel=True)
    b = coll.lookup_all(new_p, new_b, None, use_kernel=True, rows=jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rows_path_never_reads_pointer_buffers():
    """DESIGN.md §4's pod contract: with host-translated rows the device
    program must not consume the (c, d1) pointer tables — asserted by the
    NoDeviceGatherOf audit rule (ptr/hs invars appear in no equation; the
    rule also refuses vacuously if no input matches the names)."""
    from repro.analysis import AuditProgram, NoDeviceGatherOf

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(7), cfg)
    from repro.data import HostTranslator

    tr = HostTranslator(coll, buffers["emb"])
    rng = np.random.default_rng(7)
    sparse = np.stack(
        [rng.integers(0, v, 9) for v in cfg.vocab_sizes], axis=1
    ).astype(np.int32)
    rows = jnp.asarray(tr.rows(sparse))

    prog = AuditProgram.capture(
        lambda p, b, r: coll.lookup_all(p, b, None, use_kernel=True, rows=r),
        params["emb"], buffers["emb"], rows, name="rows_lookup",
    )
    assert NoDeviceGatherOf(("ptr", "hs")).check(prog) == []


def test_drop_sparse_rejected_when_tables_are_not_all_fused():
    """drop_sparse=True on a collection with non-universal groups would
    crash the lookup far from the cause — the translator refuses up
    front."""
    from repro.data import HostTranslator

    tables = (CCE(d1=10_000, d2=16, k=16, c=4), FullTable(100_000, 16))
    coll = EmbeddingCollection.build(tables)
    assert any(g.kind != "univ" for g in coll.groups)
    params, buffers = coll.init(jax.random.PRNGKey(0))
    tr = HostTranslator(coll, buffers)
    batch = {"sparse": np.zeros((4, 2), np.int32)}
    with pytest.raises(ValueError, match="universally fused"):
        tr(batch, drop_sparse=True)
    assert "rows" in tr(batch)  # keeping raw ids stays fine


def test_trainer_refreshes_translator_across_transitions():
    """A Trainer fed host-translated batches must produce BIT-identical
    training to the raw-ids path across a clustering transition — the
    Trainer(translator=) hook re-syncs the ptr/hs mirrors the moment the
    transition rewrites them."""
    from repro.data import ClickstreamConfig, HostTranslator, clickstream_batches
    from repro.data import translate_batches
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)

    def run(host_rows: bool):
        params, buffers = dlrm.init(jax.random.PRNGKey(21), cfg)
        dyn, static = split_buffers(buffers)
        opt = sgd(momentum=0.9)

        def loss_fn(p, b, mb):
            return dlrm.bce_loss(p, b, cfg, mb), {}

        step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
        state = init_state(params, opt, dyn)
        data = clickstream_batches(
            ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=21), 16
        )

        def cluster_fn(key, p, b):
            return dlrm.cluster_tables(key, p, b, cfg)

        translator = None
        if host_rows:
            translator = HostTranslator(cfg.collection, buffers["emb"])
            data = translate_batches(data, translator, drop_sparse=True)
        tr = Trainer(
            jax.jit(step, donate_argnums=(0,)), state, static, data,
            cluster_fn=cluster_fn, cluster_every=4, cluster_max=2,
            translator=translator, seed=21,
        )
        tr.run(10)
        assert tr.clusters_done == 2
        return tr.state

    s_rows, s_ids = run(True), run(False)
    for a, b in zip(jax.tree.leaves(s_rows.params), jax.tree.leaves(s_ids.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_translate_batches_ships_rows_only():
    """The translated batch is the only sparse input shipped: the wrapper
    drops raw ids and the model consumes rows."""
    from repro.data import ClickstreamConfig, HostTranslator, clickstream_batches
    from repro.data import translate_batches

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(8), cfg)
    tr = HostTranslator(cfg.collection, buffers["emb"])
    raw_it = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=8), 16
    )
    raw = next(
        clickstream_batches(
            ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=8), 16
        )
    )
    batch = next(translate_batches(raw_it, tr, drop_sparse=True))
    assert "sparse" not in batch and batch["rows"].dtype == np.int32
    out_rows = dlrm.forward(params, buffers, cfg, batch)
    out_ids = dlrm.forward(params, buffers, cfg, raw)
    np.testing.assert_array_equal(np.asarray(out_rows), np.asarray(out_ids))


def test_stack_unstack_roundtrip_bitexact():
    cfg = MIXED
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(2), cfg)
    rt = coll.stack_params(coll.unstack_params(params["emb"]))
    for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(params["emb"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rt_b = coll.stack_buffers(coll.unstack_buffers(buffers["emb"]))
    assert jax.tree.structure(rt_b) == jax.tree.structure(buffers["emb"])
    # per-feature views agree with unstack
    per = coll.unstack_params(params["emb"])
    for i in range(coll.n_features):
        for a, b in zip(
            jax.tree.leaves(coll.feature_params(params["emb"], i)),
            jax.tree.leaves(per[i]),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- legacy checkpoint migration ------------------------------------------


def test_legacy_per_feature_checkpoint_restores_bitexact(tmp_path):
    """A checkpoint written under the pre-collection layout (params/moments/
    ebuf per feature) restores bit-exact into the grouped state through
    Trainer.restore_latest + dlrm.checkpoint_migrations."""
    from repro.checkpoint import save_checkpoint
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(3), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=3), 16
    )
    tr = Trainer(
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=str(tmp_path), migrations=dlrm.checkpoint_migrations(cfg),
    )
    tr.run(3)

    # hand-write what a PR-2-era writer produced: per-feature emb trees
    to_old, _ = dlrm.checkpoint_migrations(cfg)[0]
    new_tree = {"state": tr.state, "clusters_done": np.int32(0)}
    old_tree = to_old(new_tree)
    # sanity: the legacy layout really is per-feature (one leaf per table)
    assert len(old_tree["state"].params["emb"]) == cfg.n_sparse
    save_checkpoint(str(tmp_path), 3, old_tree)

    want = jax.tree.leaves(tr.state)
    assert tr.restore_latest() == 3
    for a, b in zip(jax.tree.leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and training continues from the migrated state
    tr.run(2)
    assert np.isfinite(tr.history[-1]["loss"])


def test_legacy_checkpoint_with_id_counts_and_trackerless_reader(tmp_path):
    """Hardest migration case: the legacy writer ALSO checkpointed id
    histograms, and the restoring Trainer has no tracker — the id_counts
    wildcard placeholder must be sized against the CONVERTED (per-feature)
    layout, not the grouped one."""
    from repro.checkpoint import save_checkpoint
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.train.freq import IdFrequencyTracker
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(7), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=7), 16
    )
    tr = Trainer(  # NO id_tracker
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=str(tmp_path), migrations=dlrm.checkpoint_migrations(cfg),
    )
    tr.run(2)
    to_old, _ = dlrm.checkpoint_migrations(cfg)[0]
    old_tree = to_old({"state": tr.state, "clusters_done": np.int32(1)})
    # the legacy writer's tracker state rides along
    tracker = IdFrequencyTracker(cfg.vocab_sizes)
    old_tree["id_counts"] = tracker.state_tree()
    save_checkpoint(str(tmp_path), 2, old_tree)

    want = jax.tree.leaves(tr.state)
    assert tr.restore_latest() == 2
    assert tr.clusters_done == 1
    for a, b in zip(jax.tree.leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pr3_grouped_checkpoint_restores_bitexact(tmp_path):
    """A checkpoint written under the PRE-UNIVERSAL grouped layout
    (mode="group": per-signature CCE slab + full buckets) restores
    bit-exact into today's universal layout through Trainer.restore_latest
    + dlrm.checkpoint_migrations."""
    from repro.checkpoint import save_checkpoint
    from repro.core.collection import grouped_layout_migration
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = MIXED  # cce + full mix: grouped and universal layouts differ
    params, buffers = dlrm.init(jax.random.PRNGKey(11), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=11), 16
    )
    tr = Trainer(
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=str(tmp_path), migrations=dlrm.checkpoint_migrations(cfg),
    )
    tr.run(3)

    # hand-write what a PR-3/PR-4-era writer produced: the mode="group"
    # grouped layout (CCE supertable + padded full stack)
    grouped = EmbeddingCollection.build(cfg.collection.tables, mode="group")
    assert len(grouped.groups) > 1  # really a different layout
    to_old, _ = grouped_layout_migration(cfg.collection, grouped)
    old_tree = to_old({"state": tr.state, "clusters_done": np.int32(0)})
    save_checkpoint(str(tmp_path), 3, old_tree)

    want = jax.tree.leaves(tr.state)
    assert tr.restore_latest() == 3
    for a, b in zip(jax.tree.leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tr.run(2)
    assert np.isfinite(tr.history[-1]["loss"])


def test_group_mode_reproduces_pr3_order():
    """mode="group" must emit groups in the HISTORICAL order (signature
    insertion + d1-sorted full buckets) — NOT first-feature order — or
    PR-3 grouped checkpoints restore into the wrong list positions.
    Pinned against the actual PR-3 build output."""
    # full spread with the largest table FIRST: PR-3 put the d1-sorted
    # small bucket before the big one
    tables = (FullTable(100_000, 16), FullTable(8, 16), FullTable(16, 16))
    grouped = EmbeddingCollection.build(tables, mode="group")
    assert [g.features for g in grouped.groups] == [(1, 2), (0,)]
    # ...and the universal (current) layout orders by first feature, so
    # the layouts differ and checkpoint_migrations must bridge them
    univ = EmbeddingCollection.build(tables)
    assert [g.features for g in univ.groups] == [(0,), (1, 2)]
    # within a full bucket PR-3 kept d1 order, not feature order
    grouped = EmbeddingCollection.build(MIXED.collection.tables, mode="group")
    full = [g for g in grouped.groups if g.kind == "full"][0]
    assert full.features == (0, 4, 2)  # d1s 8, 16, 20


def test_pr3_grouped_checkpoint_restores_bitexact_order_sensitive(tmp_path):
    """Ordering-sensitive variant: a pure-full config whose PR-3 group
    order differs from first-feature order still restores bit-exact."""
    from repro.checkpoint import save_checkpoint
    from repro.core.collection import grouped_layout_migration
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = DLRMConfig(
        vocab_sizes=(100_000, 8, 16), n_dense=13, emb_dim=16,
        bottom_mlp=(32, 16), top_mlp=(32, 1), emb_method="full",
    )
    params, buffers = dlrm.init(jax.random.PRNGKey(13), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=13), 8
    )
    tr = Trainer(
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=str(tmp_path), migrations=dlrm.checkpoint_migrations(cfg),
    )
    tr.run(2)
    grouped = EmbeddingCollection.build(cfg.collection.tables, mode="group")
    assert [g.features for g in grouped.groups] != [
        g.features for g in cfg.collection.groups
    ]
    to_old, _ = grouped_layout_migration(cfg.collection, grouped)
    save_checkpoint(
        str(tmp_path), 2, to_old({"state": tr.state, "clusters_done": np.int32(0)})
    )
    want = jax.tree.leaves(tr.state)
    assert tr.restore_latest() == 2
    for a, b in zip(jax.tree.leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_new_layout_checkpoint_still_restores(tmp_path):
    from repro.checkpoint import save_checkpoint
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.train.loop import Trainer, init_state, make_train_step, split_buffers

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(4), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    step = make_train_step(loss_fn, opt, lambda s: jnp.float32(0.05), static)
    state = init_state(params, opt, dyn)
    data = clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=4), 16
    )
    tr = Trainer(
        jax.jit(step, donate_argnums=(0,)), state, static, data,
        ckpt_dir=str(tmp_path), migrations=dlrm.checkpoint_migrations(cfg),
    )
    tr.run(2)
    save_checkpoint(str(tmp_path), 2, {"state": tr.state, "clusters_done": np.int32(0)})
    want = jax.tree.leaves(tr.state)
    assert tr.restore_latest() == 2
    for a, b in zip(jax.tree.leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- transition through the collection -------------------------------------


def test_collection_transition_equals_per_table_transition():
    """cluster_tables through the grouped layout produces EXACTLY the
    tables/pointers the per-table loop would: slice per feature and
    compare against transition_table run standalone."""
    from repro.train.transition import transition_table

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(5), cfg)
    key = jax.random.PRNGKey(6)
    p2, b2 = dlrm.cluster_tables(key, params, buffers, cfg)
    per_p = coll.unstack_params(params["emb"])
    per_b = coll.unstack_buffers(buffers["emb"])
    for i in range(cfg.n_sparse):
        t = cfg.table(i)
        if not isinstance(t, CCE):
            continue
        want_p, want_b, _ = transition_table(
            t, jax.random.fold_in(key, i), per_p[i], per_b[i],
            chunk_size=cfg.emb_cluster_chunk,
        )
        got_p = coll.feature_params(p2["emb"], i)
        got_b = coll.feature_buffers(b2["emb"], i)
        np.testing.assert_array_equal(
            np.asarray(got_p["tables"]), np.asarray(want_p["tables"])
        )
        np.testing.assert_array_equal(
            np.asarray(got_b["ptr"]), np.asarray(want_b["ptr"])
        )
        np.testing.assert_array_equal(
            np.asarray(got_b["hs"]), np.asarray(want_b["hs"])
        )


# --- sum-pooled multi-hot bags ---------------------------------------------


def _bag_config(**kw):
    return DLRMConfig(vocab_sizes=(900, 7, 5000, 300), emb_dim=16, emb_method="cce",
                      emb_param_cap=512, emb_c=4, bag_sizes=(3, 1, 1, 2), **kw)


def test_bags_sum_their_ids_in_one_launch_per_run():
    """Each feature's embedding is the sum over its bag, through the fused
    kernel and the jnp path alike; adjacent features of one bag length
    share a launch (fwd and bwd: one pallas_call per run each)."""
    from repro.analysis import count_primitive

    cfg = _bag_config()
    coll = cfg.collection
    assert [g.runs for g in coll.groups] == [((4, 3), (8, 1), (4, 2))]
    assert coll.n_lookup_launches == 3
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    sparse = jnp.asarray(np.concatenate(
        [rng.integers(0, v, (13, n)) for v, n in zip(cfg.vocab_sizes, cfg.bag_sizes)], 1),
        jnp.int32)
    per_p = coll.unstack_params(params["emb"])
    per_b = coll.unstack_buffers(buffers["emb"])
    cols = np.cumsum((0,) + cfg.bag_sizes)
    want = jnp.stack([sum(coll.tables[f].lookup(per_p[f], per_b[f], sparse[:, j])
                          for j in range(cols[f], cols[f + 1]))
                      for f in range(coll.n_features)], axis=1)
    for use_kernel in (True, False):
        got = jax.jit(lambda p: coll.lookup_all(p, buffers["emb"], sparse,
                                                use_kernel=use_kernel))(params["emb"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    grad = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        coll.lookup_all(p, buffers["emb"], sparse, use_kernel=True))))(params["emb"])
    assert count_primitive(grad, "pallas_call") == 2 * coll.n_lookup_launches


def test_bags_outside_the_universal_group_are_refused():
    with pytest.raises(ValueError, match="bag_sizes"):
        _bag_config(emb_fuse="loop").collection
    coll = _bag_config().collection
    with pytest.raises(NotImplementedError, match="bag_sizes"):
        coll.lookup_all(None, None, None, rows=jnp.zeros((2, coll.rows_n_cols, 2), jnp.int32))


def test_host_translator_refuses_bags():
    """Host translation stays one id a feature until a cell needs bags."""
    from repro.data import HostTranslator

    with pytest.raises(ValueError, match="bag_sizes"):
        HostTranslator(_bag_config().collection)
