"""Host-side pointer translation — DESIGN.md §4, wired into §6's
single-launch dataflow.

The CCE pointer tables ``(c, d1)`` are the only O(vocab) training-time
state besides uncompressed embeddings.  On a single device they live in
device memory and the row translation is a cheap fused gather; on a pod
they are HOST-resident and ride the input pipeline: this module
translates raw ids -> supertable codebook rows on the host, using
bit-exact numpy mirrors of every table's row function
(``table.fuse_rows_np``: learned-pointer gather + ``multiply_shift_np``
helper hashes for CCE, plain hashes for hash/CE tables, clamped identity
for fused full tables).  The translated batch ships ONE int32 tensor

    rows : (B, collection.rows_n_cols, collection.rows_n_tables)

— the only sparse input the device program needs (``-1`` marks padded
sub-table slots; the one-hot kernel treats them as no-ops), and the
device program never gathers the pointer tables
(``EmbeddingCollection.lookup_all(rows=...)``; asserted at the jaxpr
level in tests/test_collection.py).

The mirrors are snapshots: the clustering transition rewrites ``ptr`` /
``hs``, so ``HostTranslator.update(emb_buffers)`` must run after every
transition (and after a checkpoint restore) before translating further
batches — exactly where a pod pipeline re-broadcasts the id-sharded
pointer the sharded transition produces (§2).  Pass the translator to
``Trainer(translator=...)`` and the training loop does both re-syncs
itself (``translate_batches`` is lazy, so the next batch already uses
the fresh mirrors — host-rows training is bit-identical to raw-ids
training across transitions, tested).
"""
from __future__ import annotations

import numpy as np

from repro.core.collection import EmbeddingCollection, _expand_rows, bucket_rows
from repro.obs.trace import span


class HostTranslator:
    """ids -> supertable rows on host, bit-exact with the device path.

    With ``n_shards=M`` the translator additionally plays the ps-lite
    worker-side id-router role: each universal group's rows are bucketed
    by owning model shard (shard ``s`` owns codebook rows
    ``[s*k_pad/M, (s+1)*k_pad/M)``) and ``rows()`` emits shard-LOCAL
    indices (B, M, rows_n_cols, rows_n_tables) — the device program then
    skips the bucketing arithmetic and goes straight to all-to-all
    (``EmbeddingCollection._univ_lookup_sharded``)."""

    def __init__(self, collection: EmbeddingCollection, emb_buffers=None,
                 *, n_shards: int = 1):
        if collection.multi_hot:
            raise ValueError(
                f"HostTranslator translates one id a feature; this collection has "
                f"bag_sizes {collection.bag_sizes} (bags are translated on the device)")
        self.collection = collection
        self.n_shards = int(n_shards)
        for g in collection.univ_groups:
            grp = collection.groups[g]
            if grp.k_pad % self.n_shards:
                raise ValueError(
                    f"group {g}: k_pad {grp.k_pad} not divisible by "
                    f"n_shards {n_shards}; build the collection with "
                    f"k_multiple={n_shards}"
                )
        self._buffers = None
        if emb_buffers is not None:
            self.update(emb_buffers)

    def update(self, emb_buffers) -> None:
        """Refresh the host mirrors from the (possibly device-resident)
        buffer tree — numpy copies of every leaf the row functions read.
        Cheap for everything but the pointer tables, whose device->host
        pull is the point: afterwards the device never touches them."""
        mirrored = []
        for g, grp in enumerate(self.collection.groups):
            if grp.kind != "univ":
                mirrored.append(emb_buffers[g])
                continue
            mirrored.append(
                [
                    {k: v if isinstance(v, tuple) else np.asarray(v)
                     for k, v in feat.items()}
                    for feat in emb_buffers[g]
                ]
            )
        self._buffers = mirrored

    def rows(self, sparse: np.ndarray) -> np.ndarray:
        """(B, n_features) raw ids -> (B, rows_n_cols, rows_n_tables)
        int32 supertable rows (universal groups concatenated along the
        column axis; narrower groups' extra sub-table slots are -1).
        With ``n_shards=M`` > 1 the result gains a shard-bucket axis:
        (B, M, rows_n_cols, rows_n_tables) shard-local indices, each
        group bucketed by its own ``k_pad / M``."""
        if self._buffers is None:
            raise RuntimeError("HostTranslator.update(emb_buffers) first")
        coll = self.collection
        M = self.n_shards
        sparse = np.asarray(sparse)
        T = coll.rows_n_tables
        blocks = []
        for g in coll.univ_groups:
            grp = coll.groups[g]
            grows = np.concatenate(
                [
                    _expand_rows(
                        t.fuse_rows_np(self._buffers[g][f], sparse[:, i]),
                        grp.col_counts[f] // t.fuse_spec.cols,
                        grp.n_tables,
                        np,
                    )
                    for f, (i, t) in enumerate(zip(grp.features, grp.tables))
                ],
                axis=0,
            )  # (n_cols, B, T_g)
            if grows.shape[-1] < T:
                pad = np.full(grows.shape[:-1] + (T - grows.shape[-1],), -1,
                              np.int32)
                grows = np.concatenate([grows, pad], axis=-1)
            if M > 1:
                grows = bucket_rows(grows, grp.k_pad // M, M, np)
                # (M, n_cols, B, T)
            blocks.append(grows)
        rows = np.concatenate(blocks, axis=-3)  # col axis, with/without M
        if M > 1:
            return np.moveaxis(rows, (0, 1, 2), (1, 2, 0)).astype(np.int32)
        return np.moveaxis(rows, 0, 1).astype(np.int32)

    def rows_masked(self, sparse: np.ndarray, skip: np.ndarray) -> np.ndarray:
        """Translate like :meth:`rows`, then mask every column of a
        skipped (batch-element, feature) pair to the ``-1`` sentinel.

        ``skip`` is (B, n_features) bool — True where a serve-side cache
        already holds the decoded embedding, so the fused kernel must do
        ZERO work for that feature (the sentinel is a free no-op in the
        one-hot kernel; the cache value is added outside the launch).
        Single-shard only: the serve path has no all-to-all."""
        if self.n_shards != 1:
            raise ValueError(
                "rows_masked is a serve-path helper; it does not emit "
                f"shard-bucketed rows (n_shards={self.n_shards})"
            )
        rows = self.rows(sparse)
        m = np.asarray(skip, bool)[:, self.collection.rows_col_feature]
        return np.where(m[:, :, None], np.int32(-1), rows)

    def __call__(self, batch: dict, *, drop_sparse: bool = False) -> dict:
        """Translate one batch dict: adds ``rows``; ``drop_sparse=True``
        removes the raw ids so the translated rows are the ONLY sparse
        input shipped to the device (a tracker-carrying pipeline keeps
        them — frequency sketches hash raw ids)."""
        if drop_sparse:
            unfused = [
                g.kind for g in self.collection.groups if g.kind != "univ"
            ]
            if unfused:
                # rows only cover universal groups; the full/loop groups
                # still consume raw ids — dropping them would crash the
                # lookup far from the cause
                raise ValueError(
                    "drop_sparse=True needs every table universally fused; "
                    f"this collection still has {sorted(set(unfused))} "
                    "groups that consume raw ids"
                )
        out = dict(batch, rows=self.rows(batch["sparse"]))
        if drop_sparse:
            del out["sparse"]
        return out


def translate_batches(batches, translator: HostTranslator, *,
                      drop_sparse: bool = False):
    """Wrap a batch iterator with the host translation stage (the input
    pipeline runs on CPU hosts — see data/synthetic.py)."""
    for batch in batches:
        with span("translate"):
            out = translator(batch, drop_sparse=drop_sparse)
        yield out
