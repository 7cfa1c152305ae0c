"""EmbeddingCollection — grouped supertables for multi-feature models.

The paper's hot loop is ``concat_i M_i[h_i(id)] + M'_i[h'_i(id)]`` per
categorical feature; DLRM has 26 of them.  Issuing 26 independent gathers
per step wastes the fused one-hot-matmul kernel (``kernels/cce_lookup``)
and launches O(n_features) ops where O(1) suffices — the
``QREmbeddingBag`` lesson from Shi et al. 2020, and the precondition CAFE
(Zhang et al. 2023) names for adaptive per-feature compression to pay off.

The collection groups a model's tables by fuse compatibility and stacks
each group's parameters (DESIGN.md §3/§6):

  * UNIVERSAL groups — every method whose lookup is a per-column
    gather-sum (``table.fuse_spec``: CCE, CEConcat, HashingTrick, and
    small FullTables) stacks into ONE supertable
    (total cols, T, max k_f, dsub) and runs as ONE ``kops.cce_lookup``
    launch per step, forward AND backward.  Tables with different natural
    column widths split into sub-columns of the group gcd; tables with
    fewer than T sub-tables pad their row tensor with the ``-1`` sentinel
    (a sentinel row matches no one-hot lane: exactly-zero forward
    contribution and exactly-zero gradient).  On the compressed Criteo
    DLRM config every table joins one universal group — the whole
    embedding stack is a single heavy launch.
  * Full groups with equal (d2, dtype) — big uncompressed tables (gated
    out of universal fusion: their one-hot matmul would be O(d1) wide)
    batch into ONE padded (F, max d1, d2) gather, sub-partitioned when
    the d1 spread would make padding cost more than the fusion saves.
  * Everything else (hemb/robe/dhe/tt) falls back to a per-feature loop.

State layout (the "grouped layout", DESIGN.md §3):

    params["emb"]  : [group_params, ...]       one entry per group
    buffers["emb"] : [[feat_buffers, ...], ...]  per group, per feature

Buffers are NEVER stacked — pointer arrays have per-feature vocabularies
and stay exactly as the per-feature methods wrote them, so every CCE
method (cluster, remap_moments, materialize) applies unchanged to a
feature's slice.  ``stack_params``/``unstack_params`` convert between the
grouped layout and the legacy per-feature layout (used by the checkpoint
migration: pre-collection checkpoints restore bit-exact, see
``legacy_layout_migration``).  Stacking is value-preserving by
construction: sub-column splits are reshapes, T/codebook padding is
zeros, and padded/sentinel regions receive exactly-zero gradient so they
STAY zero under training.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embeddings as emb_lib
from repro.core.cce import CCE

#: Sub-partition a "full" group when padding every table to the group max
#: would blow past this multiple of the smallest table in the bucket —
#: bounds the padded-parameter waste at ~FULL_PAD_RATIO per bucket while a
#: budget-capped config (all small tables) still lands in one gather.
FULL_PAD_RATIO = 8

#: Universal groups pad every member's codebook axis to the group max k
#: (and its sub-table axis to the group max T), so the supertable must not
#: cost more than this multiple of the members' NATURAL parameter count —
#: otherwise one large-k member would inflate every other member's slab
#: (params, optimizer moments, AND per-column one-hot work all scale with
#: k_pad).  Buckets greedily split (largest k first) to stay inside the
#: bound; a split-off all-full bucket reverts to the padded gather.  The
#: compressed Criteo config sits well inside the bound (~1.8x) and stays
#: ONE launch.
UNIV_PAD_WASTE = 3.5

#: The aggregate bound alone would let a dominant huge-k member carry a
#: tiny member to astronomical PER-MEMBER inflation (an 8-row table padded
#: to a 100k-row codebook is megabytes of dead params and 100k-row one-hot
#: work per lookup, yet barely moves the bucket total).  So each member's
#: padded slab must ALSO stay within UNIV_PAD_WASTE of its own natural
#: size — unless the padded slab is small in ABSOLUTE terms (below this
#: many elements), where relative inflation is irrelevant: Criteo's d1=3
#: full table padded to the CCE codebook costs kilobytes and one launch
#: saved is worth far more.
UNIV_PAD_SLACK_ELEMS = 1 << 20


@dataclasses.dataclass(frozen=True)
class TableGroup:
    kind: str  # "univ" | "full" | "loop"
    features: tuple[int, ...]  # global feature indices, ascending
    tables: tuple[Any, ...]  # the features' method objects, same order
    # universal groups only: the shared sub-column width (gcd of member
    # natural dsubs) and stacked-table count (max member n_tables)
    dsub: int | None = None
    n_tables: int | None = None
    #: round the codebook axis up to a multiple of this — the model-shard
    #: count must divide k_pad so the slab splits evenly across devices.
    #: Extra rows are zero, unreachable (every row index < the natural
    #: k), and therefore zero-grad: they stay zero under training, so a
    #: k_multiple=1 and a k_multiple=M layout are bit-interconvertible
    #: (``grouped_layout_migration``).
    k_multiple: int = 1
    #: universal groups only: each member's bag length (ids summed into
    #: one embedding per example; 1 = one id)
    bags: tuple[int, ...] = ()

    @functools.cached_property
    def col_counts(self) -> tuple[int, ...]:
        """Supertable columns per feature (natural cols × dsub split)."""
        return tuple(
            t.fuse_spec.cols * (t.fuse_spec.dsub // self.dsub)
            for t in self.tables
        )

    @property
    def n_cols(self) -> int:
        return sum(self.col_counts)

    @property
    def k_pad(self) -> int:
        k = max(t.fuse_spec.k for t in self.tables)
        return -(-k // self.k_multiple) * self.k_multiple

    @functools.cached_property
    def runs(self) -> tuple[tuple[int, int], ...]:
        """(columns, bag length) of each run of adjacent supertable
        columns whose features share a bag length, in column order — one
        kernel launch each (``kops.cce_lookup``)."""
        out: list[list[int]] = []
        for n, bag in zip(self.col_counts, self.bags):
            if out and out[-1][1] == bag:
                out[-1][0] += n
            else:
                out.append([n, bag])
        return tuple((n, bag) for n, bag in out)


# --- universal-slab plumbing (shared by device + host paths) ----------------


def _split_slab(nat, dsub: int, n_tables: int):
    """Natural (c, T, k, d) slab -> group layout (c*s, T_g, k, dsub):
    each column splits into s = d/dsub sub-columns (a pure reshape —
    sub-column j of column i holds rows' [j*dsub:(j+1)*dsub] slice, so
    concatenating sub-column outputs reconstructs the original d2
    layout), then missing sub-tables zero-pad the T axis (their rows are
    the -1 sentinel: unreachable, zero-grad, stays zero)."""
    c, T, k, d = nat.shape
    s = d // dsub
    x = nat.reshape(c, T, k, s, dsub)
    x = jnp.moveaxis(x, 3, 1).reshape(c * s, T, k, dsub)
    if T < n_tables:
        x = jnp.pad(x, ((0, 0), (0, n_tables - T), (0, 0), (0, 0)))
    return x


def _merge_slab(slab, spec: emb_lib.FuseSpec, dsub: int):
    """Inverse of ``_split_slab`` (slab already sliced to the feature's
    k): drop T padding, re-interleave sub-columns."""
    s = spec.dsub // dsub
    x = slab[:, : spec.n_tables]
    x = x.reshape(spec.cols, s, spec.n_tables, x.shape[2], dsub)
    x = jnp.moveaxis(x, 1, 3).reshape(spec.cols, spec.n_tables, x.shape[3], spec.dsub)
    return x


def _expand_rows(rows, s: int, n_tables: int, xp):
    """Natural (c, B, T) rows -> group (c*s, B, T_g): sub-columns share
    their parent column's rows; padded T slots get the -1 sentinel.
    ``xp`` is numpy (host translation) or jnp (device) — bit-identical."""
    if s > 1:
        rows = xp.repeat(rows, s, axis=0)
    T = rows.shape[-1]
    if T < n_tables:
        pad = xp.full(rows.shape[:-1] + (n_tables - T,), -1, np.int32)
        rows = xp.concatenate([rows, pad.astype(rows.dtype)], axis=-1)
    return rows


def bucket_rows(rows, k_loc: int, n_shards: int, xp):
    """Route global row indices to their owning model shard.

    ``rows`` int32 with the -1 no-op sentinel, any shape; shard ``s``
    owns the contiguous codebook slice ``[s*k_loc, (s+1)*k_loc)``.
    Returns a stacked (n_shards, *rows.shape) tensor where bucket ``s``
    holds shard-LOCAL indices for the ids it owns and the -1 sentinel
    everywhere else — each global row appears in exactly one bucket, so
    summing the buckets' lookups reproduces the unsharded lookup
    exactly.  ``xp`` is numpy (host translation) or jnp (in-step device
    bucketing) — bit-identical, same twin pattern as ``_expand_rows``.
    """
    owner = rows // k_loc
    return xp.stack(
        [
            xp.where((rows >= 0) & (owner == s), rows - s * k_loc, -1)
            for s in range(n_shards)
        ],
        axis=0,
    ).astype(np.int32)


def _gcd_all(vals) -> int:
    return functools.reduce(math.gcd, vals)


@dataclasses.dataclass(frozen=True)
class EmbeddingCollection:
    tables: tuple[Any, ...]
    groups: tuple[TableGroup, ...]
    #: ids a feature looks up and sum-pools per example (1 = one id).
    #: The sparse input is (B, sum(bag_sizes)), feature f's bag in the
    #: columns from sum(bag_sizes[:f]).
    bag_sizes: tuple[int, ...]

    # --- construction ----------------------------------------------------

    @classmethod
    def build(cls, tables: Sequence[Any], mode: str = "univ",
              k_multiple: int = 1,
              bag_sizes: Sequence[int] = ()) -> "EmbeddingCollection":
        """``mode``:
        * "univ" (default) — universal fusion: every gather-sum table
          (``fuse_spec``) joins one supertable per dtype; ONE launch for
          the whole embedding stack on the Criteo config.
        * "group" — the pre-universal grouping (per-signature CCE groups
          + padded full-gather buckets); kept as the benchmark baseline.
        * "loop" — one loop group per feature (the pre-collection hot
          loop); benchmark baseline only.

        ``k_multiple`` rounds every universal group's ``k_pad`` up so a
        model mesh axis of that size divides the slab evenly (sharded
        configs set it to the shard count; layouts with different
        ``k_multiple`` stay bit-interconvertible, see ``TableGroup``).
        Historical "group"/"loop" layouts ignore it by construction.

        ``bag_sizes`` (one per table; empty = one id each) makes features
        multi-hot, sum-pooled; only universal groups look up bags, so a
        feature with a bag of more than one id must join one.
        """
        tables = tuple(tables)
        bag_sizes = tuple(int(b) for b in bag_sizes) or (1,) * len(tables)
        if len(bag_sizes) != len(tables) or min(bag_sizes, default=1) < 1:
            raise ValueError(f"bag_sizes {bag_sizes} for {len(tables)} tables")
        if mode == "loop":
            groups = tuple(
                TableGroup("loop", (i,), (t,)) for i, t in enumerate(tables)
            )
            return cls(tables, groups, bag_sizes)
        if mode not in ("univ", "group"):
            raise ValueError(f"unknown collection mode {mode!r}")

        legacy: list[int] = []  # features grouped by the pre-universal rules
        groups: list[TableGroup] = []
        if mode == "univ":
            fusable: dict[str, list[int]] = {}
            for i, t in enumerate(tables):
                if hasattr(t, "fuse_spec"):
                    fusable.setdefault(str(jnp.dtype(t.dtype)), []).append(i)
                else:
                    legacy.append(i)
            for _, feats in fusable.items():
                for bucket in cls._partition_univ(feats, tables):
                    if all(
                        isinstance(tables[i], emb_lib.FullTable) for i in bucket
                    ):
                        # full-only bucket: a one-hot matmul over k = d1
                        # rows has nothing to amortize against — keep the
                        # padded gather
                        legacy.extend(bucket)
                        continue
                    members = sorted(bucket)
                    specs = [tables[i].fuse_spec for i in members]
                    groups.append(
                        TableGroup(
                            "univ",
                            tuple(members),
                            tuple(tables[i] for i in members),
                            dsub=_gcd_all(s.dsub for s in specs),
                            n_tables=max(s.n_tables for s in specs),
                            k_multiple=k_multiple,
                            bags=tuple(bag_sizes[i] for i in members),
                        )
                    )
        else:
            legacy = list(range(len(tables)))

        by_sig: dict[Any, list[int]] = {}
        for i in legacy:
            t = tables[i]
            if mode == "group" and isinstance(t, CCE):
                sig = ("cce", t.c, t.dsub, str(jnp.dtype(t.dtype)))
            elif isinstance(t, emb_lib.FullTable):
                sig = t.group_signature()
            else:
                sig = ("loop", i)
            by_sig.setdefault(sig, []).append(i)
        for sig, feats in by_sig.items():  # insertion order: first feature
            if sig[0] == "cce":
                specs = [tables[i].fuse_spec for i in feats]
                groups.append(
                    TableGroup(
                        "univ", tuple(feats), tuple(tables[i] for i in feats),
                        dsub=_gcd_all(s.dsub for s in specs),
                        n_tables=max(s.n_tables for s in specs),
                        bags=tuple(bag_sizes[i] for i in feats),
                    )
                )
                continue
            kind = "full" if sig[0] == "full" else "loop"
            for bucket in cls._partition(kind, feats, tables):
                groups.append(
                    TableGroup(kind, tuple(bucket), tuple(tables[i] for i in bucket))
                )
        if mode == "univ":
            groups.sort(key=lambda g: g.features[0])
        # mode="group" keeps the HISTORICAL order (signature insertion +
        # d1-sorted full buckets) so its layout matches PR-3 checkpoints
        # byte for byte — grouped_layout_migration depends on this
        return cls(tables, tuple(groups), bag_sizes)

    def __post_init__(self):
        for grp in self.groups:
            if grp.kind != "univ" and any(self.bag_sizes[i] > 1 for i in grp.features):
                raise ValueError(
                    f"bag_sizes: features {grp.features} look up bags but sit in a "
                    f"{grp.kind!r} group; only the universal supertable sums bags")

    @staticmethod
    def _partition_univ(feats, tables):
        """Split a universal bucket so the padded supertable never costs
        more than ``UNIV_PAD_WASTE``× the members' natural parameters.

        Greedy, largest k first: each candidate joins the current bucket
        only while (a) the combined padded size (every member's width ×
        the bucket max T × the bucket max k) stays inside the aggregate
        bound AND (b) every member individually stays inside the bound
        (or below the UNIV_PAD_SLACK_ELEMS absolute allowance — tiny
        tables may inflate relative to themselves, never in absolute
        terms).  One huge-k member (a big hash table, a full table with
        k = d1) can therefore never inflate a small-k member's slab —
        they end up in separate buckets.  Deterministic given the table
        list."""

        def admits(members):
            specs = [tables[i].fuse_spec for i in members]
            k_pad = max(s.k for s in specs)
            T = max(s.n_tables for s in specs)
            padded = natural = 0
            for s in specs:
                w = s.cols * s.dsub
                p, n = w * T * k_pad, w * s.n_tables * s.k
                if p > UNIV_PAD_WASTE * n and p > UNIV_PAD_SLACK_ELEMS:
                    return False  # per-member inflation, large in absolute terms
                padded += p
                natural += n
            return padded <= UNIV_PAD_WASTE * natural

        order = sorted(feats, key=lambda i: (-tables[i].fuse_spec.k, i))
        buckets, cur = [], [order[0]]
        for i in order[1:]:
            if admits(cur + [i]):
                cur.append(i)
            else:
                buckets.append(cur)
                cur = [i]
        buckets.append(cur)
        return buckets

    @staticmethod
    def _partition(kind, feats, tables):
        """Split a signature bucket when padding would be pathological:
        full tables pad the VOCAB axis, so a (tiny, huge) mix is re-split
        by d1 ratio; universal groups are waste-bounded separately
        (``_partition_univ``)."""
        if kind != "full" or len(feats) <= 1:
            return [feats]
        feats = sorted(feats, key=lambda i: tables[i].d1)
        buckets, cur = [], [feats[0]]
        for i in feats[1:]:
            if tables[i].d1 > FULL_PAD_RATIO * tables[cur[0]].d1:
                buckets.append(cur)
                cur = [i]
            else:
                cur.append(i)
        buckets.append(cur)
        return buckets

    # --- shape facts ------------------------------------------------------

    @property
    def n_features(self) -> int:
        return len(self.tables)

    @property
    def multi_hot(self) -> bool:
        """Some feature looks up a bag of more than one id."""
        return max(self.bag_sizes, default=1) > 1

    @functools.cached_property
    def _bag_starts(self) -> np.ndarray:
        return np.cumsum((0,) + self.bag_sizes)

    def sparse_columns(self, features) -> np.ndarray:
        """The columns of the sparse input that hold ``features``' ids,
        each feature's bag in order."""
        s = self._bag_starts
        return np.concatenate([np.arange(s[i], s[i + 1]) for i in features])

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def n_lookup_launches(self) -> int:
        """Heavy table-lookup ops per forward pass: 1 per fused group
        (universal supertable launch or padded full gather), 1 per
        feature of a loop group — the quantity the fusion work drives
        from O(n_features) to O(1).  Asserted against a jaxpr-level
        kernel-launch count in tests/test_collection.py so a refactor
        cannot silently reintroduce the per-feature loop."""
        return sum(
            len(g.features) if g.kind == "loop" else len(g.runs) if g.kind == "univ"
            else 1 for g in self.groups
        )

    @functools.cached_property
    def _locate(self) -> dict[int, tuple[int, int]]:
        """feature index -> (group index, index within group)."""
        out = {}
        for g, grp in enumerate(self.groups):
            for f_local, i in enumerate(grp.features):
                out[i] = (g, f_local)
        return out

    @functools.cached_property
    def univ_groups(self) -> tuple[int, ...]:
        return tuple(g for g, grp in enumerate(self.groups) if grp.kind == "univ")

    @property
    def rows_n_tables(self) -> int:
        """T of the host-translated rows tensor: max over universal
        groups (narrower groups read their leading T slots)."""
        return max((self.groups[g].n_tables for g in self.univ_groups), default=0)

    @property
    def rows_n_cols(self) -> int:
        """Total supertable columns across universal groups — the rows
        tensor is (B, rows_n_cols, rows_n_tables) int32, the ONLY sparse
        input a host-translating pipeline ships (DESIGN.md §4/§6)."""
        return sum(self.groups[g].n_cols for g in self.univ_groups)

    @functools.cached_property
    def rows_col_feature(self):
        """(rows_n_cols,) int32: GLOBAL feature index owning each column
        of the host-translated rows tensor.  Lets a serve-side cache mask
        exactly the columns of a cache-hit feature to the -1 sentinel
        (``HostTranslator.rows_masked``) so the fused kernel does zero
        work for them — per-feature column spans, in the same order
        ``rows`` concatenates universal groups."""
        out = []
        for g in self.univ_groups:
            grp = self.groups[g]
            for f_local, n in enumerate(grp.col_counts):
                out.extend([grp.features[f_local]] * n)
        return np.asarray(out, np.int32)

    # --- init / stacking --------------------------------------------------

    def init(self, key):
        """Per-feature init (same fold_in(key, i) schedule as the legacy
        per-table loop, so the stacked slices are bit-identical to the
        old layout), then stack into the grouped layout."""
        per_p, per_b = [], []
        for i, t in enumerate(self.tables):
            p, b = t.init(jax.random.fold_in(key, i))
            per_p.append(p)
            per_b.append(b)
        return self.stack_params(per_p), self.stack_buffers(per_b)

    def stack_group_params(self, grp: TableGroup, params_seq):
        if grp.kind == "univ":
            from repro.kernels import ops as kops

            slabs = [
                _split_slab(t.fuse_slab(p), grp.dsub, grp.n_tables)
                for t, p in zip(grp.tables, params_seq)
            ]
            return {"tables": kops.pad_stack_tables(slabs, k_pad=grp.k_pad)}
        if grp.kind == "full":
            return emb_lib.FullTable.stack_many(grp.tables, params_seq)
        return list(params_seq)

    def unstack_group_params(self, grp: TableGroup, group_params):
        if grp.kind == "univ":
            out, off = [], 0
            for t, n in zip(grp.tables, grp.col_counts):
                spec = t.fuse_spec
                slab = group_params["tables"][off : off + n, :, : spec.k, :]
                out.append(t.unfuse_slab(_merge_slab(slab, spec, grp.dsub)))
                off += n
            return out
        if grp.kind == "full":
            return emb_lib.FullTable.unstack_many(grp.tables, group_params)
        return list(group_params)

    def stack_params(self, per_feature):
        """Legacy per-feature params list -> grouped layout."""
        return [
            self.stack_group_params(grp, [per_feature[i] for i in grp.features])
            for grp in self.groups
        ]

    def unstack_params(self, grouped):
        """Grouped layout -> legacy per-feature params list."""
        out = [None] * self.n_features
        for g, grp in enumerate(self.groups):
            per = self.unstack_group_params(grp, grouped[g])
            for f_local, i in enumerate(grp.features):
                out[i] = per[f_local]
        return out

    def stack_buffers(self, per_feature):
        """Buffers regroup only (no array surgery — see module docstring)."""
        return [[per_feature[i] for i in grp.features] for grp in self.groups]

    def unstack_buffers(self, grouped):
        out = [None] * self.n_features
        for g, grp in enumerate(self.groups):
            for f_local, i in enumerate(grp.features):
                out[i] = grouped[g][f_local]
        return out

    def feature_params(self, emb_params, i: int):
        """Per-feature view into the grouped params (tests, serving)."""
        g, f_local = self._locate[i]
        return self.unstack_group_params(self.groups[g], emb_params[g])[f_local]

    def feature_buffers(self, emb_buffers, i: int):
        g, f_local = self._locate[i]
        return emb_buffers[g][f_local]

    # --- the hot path -----------------------------------------------------

    def group_rows(self, grp: TableGroup, buffers_seq, ids):
        """Device-side row translation for one universal group: ids (B,
        sum of the members' bags) -> one (n_cols_r, B, bag_r * T) int32
        tensor per run of ``grp.runs``.  A bag's ids translate as B * bag
        single ids, and slot ``l * T + t`` holds id l's row in sub-table
        t; one id a feature gives one (n_cols, B, T) run.  Cheap int math
        (pointer gather + multiply-shift hashes) next to the heavy
        launch; the host twin is ``data.translate.HostTranslator``."""
        B, off, per = ids.shape[0], 0, []
        for f, t in enumerate(grp.tables):
            bag = grp.bags[f]
            rows = _expand_rows(
                t.fuse_rows(buffers_seq[f], ids[:, off : off + bag].reshape(-1)),
                grp.col_counts[f] // t.fuse_spec.cols, grp.n_tables, jnp,
            )  # (n_cols_f, B * bag, T)
            per.append(rows.reshape(rows.shape[0], B, bag * grp.n_tables))
            off += bag
        return tuple(  # adjacent features of one bag length: one run
            jnp.concatenate([r for _, r in run], axis=0)
            for _, run in itertools.groupby(zip(grp.bags, per), key=lambda x: x[0]))

    def _univ_lookup(self, grp: TableGroup, group_params, rows, use_kernel):
        """(n_cols, B, T) rows, or the tuple of bag runs ``group_rows``
        gives, + supertable -> (B, n_cols*dsub)."""
        from repro.kernels import ops as kops

        runs = (rows,) if hasattr(rows, "shape") else tuple(rows)
        # trace span only (HLO metadata — profiler timelines group the
        # fused lookup under one name); no effect on the jaxpr
        with jax.named_scope("emb/fused-lookup"):
            if use_kernel:
                return kops.cce_lookup(runs, group_params["tables"])
            return self._univ_lookup_jnp(group_params, runs)

    def _univ_lookup_jnp(self, group_params, runs):
        tabs = group_params["tables"]  # (C, T, k, dsub)
        T = tabs.shape[1]

        def col(tab, r):  # (T, k, dsub), (B, T)
            picked = jax.vmap(
                lambda tt, rt: tt[jnp.maximum(rt, 0)] * (rt >= 0)[:, None],
                in_axes=(0, 1),
            )(tab, r)  # (T, B, dsub) — sentinel rows contribute exact zero
            return picked.sum(axis=0)

        def one_id(tabs_r, r):  # (c, T, k, dsub), (c, B, T) -> (B, c*dsub)
            pieces = jax.vmap(col)(tabs_r, r)  # (c, B, dsub)
            return jnp.moveaxis(pieces, 0, 1).reshape(r.shape[1], -1)

        outs, c0 = [], 0
        for r in runs:  # a bag's l-th id: slots l*T .. l*T + T of the run
            tabs_r = tabs[c0 : c0 + r.shape[0]]
            outs.append(functools.reduce(jnp.add, (
                one_id(tabs_r, r[..., s : s + T]) for s in range(0, r.shape[2], T))))
            c0 += r.shape[0]
        return jnp.concatenate(outs, axis=1)

    def _univ_lookup_sharded(self, grp: TableGroup, group_params, rows,
                             use_kernel, *, mesh, model_axis, batch_axes):
        """Model-parallel universal lookup: the slab lives row(k)-sharded
        over ``model_axis``, the batch lives sharded over ``batch_axes``
        (which INCLUDE the model axis — every device works a distinct
        batch slice), and ids route to their owning shard via all-to-all.

        ``rows`` is (B, n_cols, T) global rows (bucketed on device) or
        (B, M, n_cols, T) host-bucketed shard-local rows
        (``HostTranslator(..., n_shards=M)``).  Per shard_map body:
        bucket → all-to-all (each shard receives the ids it owns from
        every peer's batch slice) → local kernel launch (non-owned slots
        are the -1 sentinel: exact-zero partials) → all-to-all back →
        sum over shards.  Both all-to-alls transpose to all-to-alls, so
        the backward pass keeps the same routing and the slab cotangent
        psums over the unmentioned batch axes automatically — forward
        AND gradient are bit-identical to the unsharded launch (tested
        in test_sharded_lookup.py).

        Varying-axes checking is off (``compat.shard_map_unchecked``:
        pallas_call carries no replication rule) — out_specs are correct
        by the argument above.
        """
        from repro import compat

        M = int(mesh.shape[model_axis])
        k_loc = grp.k_pad // M
        if k_loc * M != grp.k_pad:
            raise ValueError(
                f"k_pad {grp.k_pad} not divisible by model shards {M}; "
                f"build the collection with k_multiple={M}"
            )
        T_g = grp.n_tables
        n_cols = grp.n_cols
        P = jax.sharding.PartitionSpec
        pre_bucketed = rows.ndim == 4

        def body(slab_loc, rows_loc):
            # slab_loc (n_cols, T, k_loc, dsub); rows_loc (B_loc, n_cols, T)
            # global rows or (B_loc, M, n_cols, T) shard-local buckets
            with jax.named_scope("emb/route"):
                if pre_bucketed:
                    b = jnp.moveaxis(rows_loc, 1, 0)  # (M, B_loc, n_cols, T)
                else:
                    b = bucket_rows(rows_loc, k_loc, M, jnp)
                recv = jax.lax.all_to_all(
                    b, model_axis, split_axis=0, concat_axis=0
                )
            B_loc = rows_loc.shape[0]
            r = jnp.moveaxis(recv.reshape(M * B_loc, n_cols, T_g), 0, 1)
            part = self._univ_lookup(grp, {"tables": slab_loc}, r, use_kernel)
            part = part.reshape(M, B_loc, n_cols * grp.dsub)
            with jax.named_scope("emb/route-back"):
                back = jax.lax.all_to_all(
                    part, model_axis, split_axis=0, concat_axis=0
                )
            return back.sum(axis=0)  # (B_loc, n_cols*dsub)

        rows_spec = P(batch_axes, *([None] * (rows.ndim - 1)))
        return compat.shard_map_unchecked(
            body,
            mesh=mesh,
            in_specs=(P(None, None, model_axis, None), rows_spec),
            out_specs=P(batch_axes, None),
        )(group_params["tables"], rows)

    def lookup_all(self, emb_params, emb_buffers, sparse, *, use_kernel=True,
                   rows=None, mesh=None, model_axis=None,
                   batch_axes=None):
        """All features' embeddings in O(n_groups) heavy lookups — ONE on
        the compressed Criteo config.

        sparse (B, sum(bag_sizes)) int32 -> (B, n_features, d2), each
        feature's embedding the sum over its bag (sparse is (B,
        n_features) with one id a feature).  Universal
        groups route through the fused Pallas kernel when ``use_kernel``
        (Mosaic on TPU, interpret mode on CPU); ``use_kernel=False`` is
        the masked-gather jnp path — identical math, used as the numerics
        oracle and as the GPU fallback.

        ``rows`` (B, rows_n_cols, rows_n_tables) int32 — HOST-translated
        row indices (``data.translate``): universal groups consume their
        column slice directly and the device program never touches the
        (c, d1) pointer buffers.  ``sparse`` may then be None when every
        feature is universally fused.

        ``mesh``/``model_axis``/``batch_axes`` switch universal groups to
        the model-parallel path (``_univ_lookup_sharded``): the slab is
        k-sharded over ``model_axis``, host rows may additionally arrive
        pre-bucketed as (B, n_shards, rows_n_cols, rows_n_tables).  Axis
        names are plain strings supplied by the caller (canonically
        ``launch.mesh.DATA_AXIS``/``MODEL_AXIS`` — core stays
        launch-agnostic).  The 1-device path is untouched.
        """
        sharded = mesh is not None and model_axis is not None
        if not sharded and rows is not None and rows.ndim == 4:
            raise ValueError("pre-bucketed 4-d rows require a model mesh")
        if self.multi_hot and (sharded or rows is not None):
            raise NotImplementedError(
                "bag_sizes: bags are looked up from raw ids on one device only")
        outs = [None] * self.n_features
        col_off = 0
        for g, grp in enumerate(self.groups):
            if grp.kind == "univ":
                if sharded:
                    if rows is None:
                        raise NotImplementedError(
                            "sharded lookup needs host-translated rows "
                            "(the device program must not gather ptr)"
                        )
                    sl = (slice(None), slice(col_off, col_off + grp.n_cols),
                          slice(None, grp.n_tables))
                    grows = rows[(slice(None), slice(None)) + sl[1:]] \
                        if rows.ndim == 4 else rows[sl]
                    col_off += grp.n_cols
                    flat = self._univ_lookup_sharded(
                        grp, emb_params[g], grows, use_kernel,
                        mesh=mesh, model_axis=model_axis,
                        batch_axes=batch_axes,
                    )
                elif rows is not None:
                    grows = jnp.moveaxis(
                        rows[:, col_off : col_off + grp.n_cols, : grp.n_tables],
                        0, 1,
                    )  # (n_cols, B, T)
                    col_off += grp.n_cols
                    flat = self._univ_lookup(grp, emb_params[g], grows, use_kernel)
                else:
                    ids = jnp.take(sparse, jnp.asarray(self.sparse_columns(grp.features)),
                                   axis=1)
                    grows = self.group_rows(grp, emb_buffers[g], ids)
                    flat = self._univ_lookup(grp, emb_params[g], grows, use_kernel)
                off = 0
                for f_local, i in enumerate(grp.features):
                    n = grp.col_counts[f_local]
                    outs[i] = flat[:, off * grp.dsub : (off + n) * grp.dsub]
                    off += n
                continue
            ids = jnp.take(sparse, jnp.asarray(self.sparse_columns(grp.features)),
                           axis=1)  # (B, Fg)
            if grp.kind == "full":
                vecs = emb_lib.FullTable.lookup_many(
                    grp.tables, emb_params[g], emb_buffers[g], ids
                )
            else:
                vecs = emb_lib.lookup_many_loop(
                    grp.tables, emb_params[g], emb_buffers[g], ids
                )
            for f_local, i in enumerate(grp.features):
                outs[i] = vecs[:, f_local]
        return jnp.stack(outs, axis=1)


def _emb_layout_migration(old_p, old_b, new_p, new_b):
    """(to_old, to_new) pair converting a checkpoint tree's embedding
    subtrees (params["emb"] / optimizer moment slots / err, and
    ebuf["emb"]) between two layouts via the given emb-tree transforms.
    Every transform is value-preserving (unstack slices bit-identical
    blocks; stacking only reshapes and pads with zeros that training
    provably keeps zero), so restores through a migration are BIT-EXACT.
    """

    def _emb(tree, fn):
        return dict(tree, emb=fn(tree["emb"])) if isinstance(tree, dict) and "emb" in tree else tree

    def _state(state, pfn, bfn):
        opt = state.opt
        if isinstance(opt, dict):
            opt = {k: _emb(v, pfn) if isinstance(v, dict) else v for k, v in opt.items()}
        return state._replace(
            params=_emb(state.params, pfn),
            opt=opt,
            ebuf=_emb(state.ebuf, bfn),
            err=_emb(state.err, pfn) if isinstance(state.err, dict) else state.err,
        )

    def to_old(tree):
        return dict(tree, state=_state(tree["state"], old_p, old_b))

    def to_new(tree):
        return dict(tree, state=_state(tree["state"], new_p, new_b))

    return to_old, to_new


def legacy_layout_migration(coll: EmbeddingCollection):
    """Checkpoint migration pair for pre-collection (per-feature) layouts:
    ``to_old(new_template)`` derives the legacy template a per-table-era
    writer produced (params["emb"] / optimizer moments / err per feature,
    ebuf per feature), ``to_new(old_tree)`` re-stacks a restored legacy
    tree into the grouped layout — bit-exact, tested in
    test_collection.py."""
    return _emb_layout_migration(
        coll.unstack_params, coll.unstack_buffers,
        coll.stack_params, coll.stack_buffers,
    )


def grouped_layout_migration(coll: EmbeddingCollection,
                             old_coll: EmbeddingCollection):
    """Checkpoint migration pair between two GROUPED layouts — e.g. a
    checkpoint written under the pre-universal grouping
    (``build(mode="group")``: per-signature CCE slab + full buckets)
    restoring into today's universal layout.  Both layouts convert
    losslessly through the per-feature view, so the restore is bit-exact
    (tested in test_collection.py)."""
    return _emb_layout_migration(
        lambda emb: old_coll.stack_params(coll.unstack_params(emb)),
        lambda emb: old_coll.stack_buffers(coll.unstack_buffers(emb)),
        lambda emb: coll.stack_params(old_coll.unstack_params(emb)),
        lambda emb: coll.stack_buffers(old_coll.unstack_buffers(emb)),
    )
