"""Clustered Compositional Embeddings — Algorithm 3 of the paper.

A CCE table with vocabulary ``d1``, output dim ``d2``, ``c`` columns and
``2k`` rows per column (main table M indexed by a *learned* pointer array
``h`` + helper table M' indexed by a *random* hash ``h'``):

    lookup(id) = concat_i( M_i[h_i(id)] + M'_i[h'_i(id)] )

``cluster()`` is the paper's training-time transition (Alg. 3, lines 10-17):
per column, materialize (a sample of) the current vocab embeddings, K-means
them into k centroids, set ``h_i <- assignments``, ``M_i <- centroids``,
draw a fresh random ``h'_i`` and zero ``M'_i``.  The helper table restores
the ability to differentiate ids the clustering merged; the next clustering
can undo bad merges.

State layout (chosen for the TPU kernels and for sharding):

    params["tables"]  : (c, 2, k, dsub) — [:,0] main M, [:,1] helper M'
    buffers["ptr"]    : (c, d1) int32   — learned pointer arrays h_i
    buffers["hs"]     : (c, 2) uint32   — multiply-shift coeffs for h'_i
    buffers["epoch"]  : () int32        — transition counter (keys cluster())

All three buffers are ARRAYS and change on cluster(); they must ride the
train state dynamically (python-int leaves would be closed over statically
by the jitted step and go stale after a transition).

The pointer arrays are plain int32 tensors: on a pod they are host-resident
and ride the input pipeline (ids are translated to per-column rows on host,
see DESIGN.md §4); on a single device they are gathered on device.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embeddings as emb_lib
from repro.core import hashing
from repro.core import kmeans as km
from repro.kernels import ops as kops
from repro.launch.mesh import DATA_AXIS


@dataclasses.dataclass(frozen=True)
class CCE:
    """Algorithm 3: CCE table with ``c`` columns and ``2k`` rows/column."""

    d1: int
    d2: int
    k: int
    c: int = 4
    seed_salt: int = 0
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert self.d2 % self.c == 0, (self.d2, self.c)
        assert self.k >= 1

    @classmethod
    def from_budget(cls, d1, d2, budget, c=4, **kw):
        # 2 tables of (k, d2/c) per column -> 2*k*d2 params total
        k = max(1, min(d1, budget // (2 * d2)))
        return cls(d1, d2, k=k, c=c, **kw)

    @property
    def dsub(self) -> int:
        return self.d2 // self.c

    @property
    def n_params(self) -> int:
        return 2 * self.k * self.d2

    # --- collection grouping (DESIGN.md §3/§6) ---------------------------

    @property
    def fuse_spec(self) -> emb_lib.FuseSpec:
        """c columns of T=2 stacked sub-tables (main + helper): the
        universal-fusion shape every gather-sum method shares.  ``k`` is
        NOT part of the group key — the supertable pads ragged codebooks
        to the group max (``kops.pad_stack_tables``), so tables fuse even
        when per-table budgets differ."""
        return emb_lib.FuseSpec(cols=self.c, n_tables=2, k=self.k, dsub=self.dsub)

    def fuse_slab(self, params):
        return params["tables"]  # (c, 2, k, dsub) — already the natural slab

    def unfuse_slab(self, slab):
        return {"tables": slab}

    def fuse_rows(self, buffers, ids):
        return self._rows(buffers, ids)  # (c, B, 2)

    def fuse_rows_np(self, buffers, ids):
        """Bit-exact numpy twin of the JITTED ``fuse_rows`` — the
        host-side pointer translation (DESIGN.md §4): learned-pointer
        gather + helper hash computed against host mirrors of the
        buffers, so the device program never gathers the (c, d1) pointer
        table.  The ptr gather clamps out-of-range ids exactly like the
        XLA gather does (numpy would raise where the device clamps); the
        helper hash consumes the RAW id, also matching the device."""
        ids = np.asarray(ids)
        ptr = np.asarray(buffers["ptr"])
        hs = np.asarray(buffers["hs"])  # (c, 2) uint32
        main = ptr[:, np.clip(ids, 0, self.d1 - 1)]  # (c, B)
        helper = hashing.multiply_shift_np(
            ids[None], hs[:, :1], hs[:, 1:], self.k
        )  # (c, B)
        return np.stack([main, helper], axis=-1).astype(np.int32)

    # --- init -----------------------------------------------------------

    def init_buffers(self):
        """Device-free buffer init (numpy): hash coefficients derive from
        ``seed_salt`` so abstract (eval_shape) and real inits agree, and the
        pointer table never touches a device mesh.

        Every buffer is an ARRAY (``hs`` a (c, 2) uint32 coefficient pack,
        ``epoch`` a 0-d int32): the transition rewrites all three, and only
        array leaves ride ``TrainState.ebuf`` through the jitted step —
        python ints would be closed over statically and the step would keep
        training against the pre-transition hash functions."""
        ptr_hashes = hashing.make_hashes(self.seed_salt * 7919 + 66, self.c, self.k)
        ids = np.arange(self.d1)
        ptr = np.stack([h.np(ids) for h in ptr_hashes])  # (c, d1) int32
        hs = hashing.pack_hashes(
            hashing.make_hashes(self.seed_salt * 7919 + 77, self.c, self.k)
        )
        return {"ptr": ptr, "hs": hs, "epoch": np.int32(0)}

    def init(self, key):
        km_ = jax.random.fold_in(key, self.seed_salt)
        scale = 1.0 / math.sqrt(self.d2)
        tables = (
            jax.random.normal(km_, (self.c, 2, self.k, self.dsub)) * scale
        ).astype(self.dtype)
        buffers = self.init_buffers()
        return {"tables": tables}, dict(buffers, ptr=jnp.asarray(buffers["ptr"]))

    # --- lookup ---------------------------------------------------------

    def _helper_rows(self, buffers, ids):
        hs = jnp.asarray(buffers["hs"])  # (c, 2) uint32, possibly traced
        shape = (self.c,) + (1,) * jnp.ndim(ids)
        return hashing.multiply_shift(
            ids[None], hs[:, 0].reshape(shape), hs[:, 1].reshape(shape), self.k
        )  # (c, ...)

    def _rows(self, buffers, ids):
        """(c, ..., 2) int32 — main rows from the learned ptr, helper rows
        from the random hash."""
        main = buffers["ptr"][:, ids]  # (c, ...)
        helper = self._helper_rows(buffers, ids)
        return jnp.stack([main, helper], axis=-1)

    def lookup(self, params, buffers, ids, *, use_kernel: bool = False):
        rows = self._rows(buffers, ids)  # (c, ..., 2)
        if use_kernel:
            flat = rows.reshape(self.c, -1, 2)
            out = kops.cce_lookup(flat, params["tables"])  # (B, c*dsub)
            return out.reshape(*ids.shape, self.d2)
        tabs = params["tables"]  # (c, 2, k, dsub)
        main = jax.vmap(lambda t, r: t[r])(tabs[:, 0], rows[..., 0])
        helper = jax.vmap(lambda t, r: t[r])(tabs[:, 1], rows[..., 1])
        pieces = main + helper  # (c, ..., dsub)
        return jnp.moveaxis(pieces, 0, -2).reshape(*ids.shape, self.d2)

    def logits(self, params, buffers, h):
        """Factored output head: per column a k-sized matmul + int gather.

        logits[b, v] = sum_i  scores_i[b, h_i(v)] + scores'_i[b, h'_i(v)]
        where scores_i = h_col_i @ M_i^T   (B, k).
        """
        hc = h.reshape(*h.shape[:-1], self.c, self.dsub)
        all_ids = jnp.arange(self.d1)
        rows = self._rows({"ptr": buffers["ptr"], "hs": buffers["hs"]}, all_ids)
        out = 0.0
        for i in range(self.c):
            scores = hc[..., i, :] @ params["tables"][i].reshape(
                2 * self.k, self.dsub
            ).T  # (..., 2k)
            out = out + scores[..., rows[i, :, 0]]
            out = out + scores[..., self.k + rows[i, :, 1]]
        return out

    # --- the clustering transition (Alg. 3 lines 10-17) ------------------

    def materialize(self, params, buffers, ids):
        """Current embeddings of ``ids``, per column: (c, n, dsub)."""
        rows = self._rows(buffers, ids)
        tabs = params["tables"]
        return jax.vmap(lambda t, r: t[r])(
            tabs[:, 0], rows[..., 0]
        ) + jax.vmap(lambda t, r: t[r])(tabs[:, 1], rows[..., 1])

    def _id_chunks(self, chunk_size: int | None):
        """Full-vocab id ranges: one range when unchunked, else a stream of
        ``chunk_size`` slices so (c, d1, dsub) is never materialized."""
        if not chunk_size or chunk_size >= self.d1:
            yield jnp.arange(self.d1)
            return
        for s in range(0, self.d1, chunk_size):
            yield jnp.arange(s, min(s + chunk_size, self.d1))

    def assign_all(
        self,
        params,
        buffers,
        centroids: jax.Array,
        *,
        chunk_size: int | None = None,
        use_kernel: bool | None = None,
    ) -> jax.Array:
        """Single-pass full-vocab nearest-centroid assignment.

        ``centroids`` (c, k, dsub) -> (c, d1) int32.  The vocabulary is
        materialized exactly once (Alg. 3 line 13), in ``chunk_size`` id
        slices; per chunk the assignment routes through the Pallas
        ``kmeans_assign`` kernel when ``use_kernel`` (default: on TPU
        only — the kernel carries its (min, argmin) accumulator across
        the k grid axis, which needs TPU's sequential grid; GPU gets the
        jnp argmin path).  Chunking is bit-exact: distances are computed
        row-wise, so the chunk boundaries cannot change any argmin.
        """
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        outs = []
        for ids in self._id_chunks(chunk_size):
            emb = self.materialize(params, buffers, ids)  # (c, n, dsub)
            outs.append(
                jnp.stack(
                    [
                        km.assign(emb[i], centroids[i], use_kernel=use_kernel)
                        for i in range(self.c)
                    ]
                )
            )
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _finish_transition(self, key, centroids, assignments, buffers):
        """Common tail of cluster()/cluster_sharded(): install centroids as
        the main tables, zero the helper tables (Alg. 3 line 17), draw
        fresh helper hashes, advance the epoch."""
        tables = jnp.stack(
            [centroids.astype(self.dtype), jnp.zeros_like(centroids, self.dtype)],
            axis=1,
        )  # (c, 2, k, dsub)
        hs = hashing.pack_hashes(
            hashing.make_hashes(jax.random.fold_in(key, 777), self.c, self.k)
        )
        new_buffers = {
            "ptr": assignments,
            "hs": hs,
            "epoch": jnp.asarray(buffers["epoch"], jnp.int32) + 1,
        }
        return {"tables": tables}, new_buffers

    def cluster(
        self,
        key,
        params,
        buffers,
        *,
        sample_ids: jax.Array | None = None,
        sample_weights: jax.Array | None = None,
        niter: int = 50,
        max_points_per_centroid: int = 256,
        chunk_size: int | None = None,
        use_kernel: bool | None = None,
    ):
        """One CCE iteration: returns new (params, buffers).

        K-means runs on a sample (FAISS-style, 256 pts/centroid by default,
        paper §Reproducibility); assignments for the FULL vocab are then
        ONE materialization pass shared by all columns (``assign_all``) —
        the per-column recompute this replaces was O(c²·d1·dsub).

        ``sample_weights`` (aligned with ``sample_ids``) runs COUNT-WEIGHTED
        k-means: each observed id appears once, weighted by its frequency —
        the zero-variance form of the paper's epoch-boundary sample (a
        with-replacement draw from the same histogram converges to it).
        """
        k1, k2 = jax.random.split(jax.random.fold_in(key, buffers["epoch"]))
        if sample_ids is None:
            sample_ids = km.subsample(k1, self.d1, self.k, max_points_per_centroid)

        sample = self.materialize(params, buffers, sample_ids)  # (c, n, dsub)
        centroids = jnp.stack(
            [
                km.kmeans(
                    jax.random.fold_in(k2, i), sample[i], self.k, niter=niter,
                    weights=sample_weights,
                ).centroids
                for i in range(self.c)
            ]
        )  # (c, k, dsub)
        new_ptr = self.assign_all(
            params, buffers, centroids, chunk_size=chunk_size, use_kernel=use_kernel
        )
        return self._finish_transition(k2, centroids, new_ptr, buffers)

    def _ptr_padded(self, ptr, d1_pad: int):
        """(c, d1) -> (c, d1_pad), tail repeating the last column so an
        even id-axis shard exists; padded entries are either masked out
        or produce row-wise duplicates that change no result."""
        ptr = jnp.asarray(ptr)
        if d1_pad > self.d1:
            ptr = jnp.concatenate(
                [ptr, jnp.tile(ptr[:, -1:], (1, d1_pad - self.d1))], axis=1
            )
        return ptr

    def materialize_sharded(self, params, buffers, ids, mesh, *,
                            axis_name: str = DATA_AXIS):
        """``materialize`` for arbitrary (scattered) ids against an
        ID-SHARDED pointer table — no shard ever holds the full (c, d1)
        ptr.  Shard ``s`` owns the contiguous id slice
        ``[s*d1_loc, (s+1)*d1_loc)``: it gathers main rows for the sample
        ids it owns, zeros the rest, and a psum assembles the full main
        part on every shard (exactly one non-zero term per id, so the
        sum is bit-exact regardless of reduction order).  The helper
        part needs only the tiny (c, 2) hash pack and is computed
        replicated; main + helper keeps ``materialize``'s addition
        order, so a 1-device axis reproduces it bit-exactly."""
        from jax.sharding import PartitionSpec as P

        from repro import compat

        nsh = mesh.shape[axis_name]
        d1_loc = (self.d1 + nsh - 1) // nsh
        ptr = self._ptr_padded(buffers["ptr"], d1_loc * nsh)
        hs = jnp.asarray(buffers["hs"])
        tabs = params["tables"]

        def body(ptr_local):
            lo = jax.lax.axis_index(axis_name) * d1_loc
            owned = (ids >= lo) & (ids < lo + d1_loc)
            local = jnp.clip(ids - lo, 0, d1_loc - 1)
            main_rows = ptr_local[:, local]  # (c, n)
            main = jax.vmap(lambda t, r: t[r])(tabs[:, 0], main_rows)
            main = jnp.where(owned[None, :, None], main, 0)
            main = jax.lax.psum(main, axis_name)
            helper = jax.vmap(lambda t, r: t[r])(
                tabs[:, 1], self._helper_rows({"hs": hs}, ids)
            )
            return main + helper

        return compat.shard_map_unchecked(
            body, mesh=mesh, in_specs=(P(None, axis_name),), out_specs=P(),
        )(ptr)

    def cluster_sharded(
        self,
        key,
        params,
        buffers,
        mesh,
        *,
        axis_name: str = DATA_AXIS,
        sample_ids: jax.Array | None = None,
        sample_weights: jax.Array | None = None,
        niter: int = 50,
        max_points_per_centroid: int = 256,
        chunk_size: int | None = None,
        use_kernel: bool | None = None,
    ):
        """Distributed transition: BOTH phases run data-parallel over
        ``axis_name``, and the (c, d1) pointer table only ever appears
        ID-SHARDED (``no-replicated-param`` holds at error severity for
        the captured transition programs).  The sample phase assembles
        the sample embeddings from the sharded ptr via masked psum
        (``materialize_sharded``); the k-means phase shards the sample
        points (local (sum, count) moments + psum — see
        ``kmeans.distributed_kmeans``); the full-vocab assignment phase
        shards the id range (``assign_all_sharded``) and returns the
        complete (c, d1) pointer as one global array, sharded over ids,
        gathered only where a consumer needs remote rows.  Sample
        weights shard with the points.  On a 1-device axis this
        reproduces ``cluster()`` exactly (same key schedule; the
        collectives degenerate to identity)."""
        from jax.sharding import PartitionSpec as P

        from repro import compat

        nsh = mesh.shape[axis_name]
        k1, k2 = jax.random.split(jax.random.fold_in(key, buffers["epoch"]))
        if sample_ids is None:
            sample_ids = km.subsample(k1, self.d1, self.k, max_points_per_centroid)
        # shard the sample evenly; the (< nsh) remainder is dropped, which
        # FAISS-style subsampling tolerates by construction
        n = sample_ids.shape[0] - sample_ids.shape[0] % nsh
        sample = self.materialize_sharded(
            params, buffers, sample_ids[:n], mesh, axis_name=axis_name
        )  # (c, n, dsub)
        w = None if sample_weights is None else sample_weights[:n].astype(jnp.float32)

        def per_shard(sample_local, w_local):
            return jnp.stack(
                [
                    km.distributed_kmeans(
                        jax.random.fold_in(k2, i),
                        sample_local[i],
                        self.k,
                        axis_name,
                        niter=niter,
                        weights=None if w_local is None else w_local,
                    )[0]
                    for i in range(self.c)
                ]
            )

        if w is None:
            centroids = compat.shard_map(
                lambda s: per_shard(s, None), mesh=mesh,
                in_specs=P(None, axis_name), out_specs=P(),
            )(sample)
        else:
            centroids = compat.shard_map(
                per_shard, mesh=mesh,
                in_specs=(P(None, axis_name), P(axis_name)), out_specs=P(),
            )(sample, w)
        new_ptr = self.assign_all_sharded(
            params, buffers, centroids, mesh, axis_name=axis_name,
            chunk_size=chunk_size, use_kernel=use_kernel,
        )
        return self._finish_transition(k2, centroids, new_ptr, buffers)

    def assign_all_sharded(
        self,
        params,
        buffers,
        centroids: jax.Array,
        mesh,
        *,
        axis_name: str = DATA_AXIS,
        chunk_size: int | None = None,
        use_kernel: bool | None = None,
    ) -> jax.Array:
        """``assign_all`` with the id range sharded over ``axis_name``.

        Each shard materializes and assigns d1/nsh ids (streamed in
        ``chunk_size`` slices like the serial pass) — the full-vocab pass
        is the transition's only O(d1) step, and it now scales with the
        data axis instead of running replicated on every host.  The OLD
        pointer table enters as a SHARDED operand (``P(None, axis)``):
        shard ``s`` owns the contiguous id slice ``[s*d1_loc,
        (s+1)*d1_loc)``, and because ptr is indexed by id, its local tile
        ``ptr[:, lo:hi]`` IS exactly the main rows of the ids the shard
        assigns — no shard ever holds the full (c, d1) table.  The
        per-shard (c, d1/nsh) tiles come back through
        ``out_specs=P(None, axis)``, i.e. the returned pointer is the
        full (c, d1) table as ONE global array sharded over the id axis
        — XLA inserts the all-gather lazily where a consumer needs rows
        from other shards.  The tail is padded with clamped ids and
        edge-repeated ptr columns (assignments are computed row-wise, so
        the padded duplicates change nothing) and sliced off after."""
        from jax.sharding import PartitionSpec as P

        from repro import compat

        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        nsh = mesh.shape[axis_name]
        d1_pad = ((self.d1 + nsh - 1) // nsh) * nsh
        ids = jnp.minimum(jnp.arange(d1_pad), self.d1 - 1)
        ptr = self._ptr_padded(buffers["ptr"], d1_pad)
        hs = jnp.asarray(buffers["hs"])
        tabs = params["tables"]

        def _chunk_assign(main_rows, ids_chunk):
            helper_rows = self._helper_rows({"hs": hs}, ids_chunk)

            # one column at a time, so one (n, k) distance block is live
            def column(i, out):
                emb = tabs[i, 0][main_rows[i]] + tabs[i, 1][helper_rows[i]]
                a = km.assign(emb, centroids[i], use_kernel=use_kernel)
                return out.at[i].set(a)

            return jax.lax.fori_loop(0, self.c, column, jnp.zeros_like(main_rows))

        def per_shard(ids_local, ptr_local):
            n_local = ids_local.shape[0]
            step = chunk_size if chunk_size and chunk_size < n_local else n_local
            if step == n_local:
                return _chunk_assign(ptr_local, ids_local)

            # a loop over chunks keeps one chunk's (n, k) distances live;
            # the last start clamps to n_local - step, so the tail chunk
            # overlaps its neighbour and rewrites the same assignments
            def body(j, out):
                s = jnp.minimum(j * step, n_local - step)
                rows = jax.lax.dynamic_slice_in_dim(ptr_local, s, step, axis=1)
                ids_c = jax.lax.dynamic_slice_in_dim(ids_local, s, step)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, _chunk_assign(rows, ids_c), s, axis=1
                )

            return jax.lax.fori_loop(
                0, -(-n_local // step), body, jnp.zeros_like(ptr_local)
            )

        ptr_new = compat.shard_map_unchecked(
            per_shard, mesh=mesh, in_specs=(P(axis_name), P(None, axis_name)),
            out_specs=P(None, axis_name),
        )(ids, ptr)
        return ptr_new[:, : self.d1]

    def assignment_counts(self, buffers) -> jax.Array:
        """Per-cluster id counts (c, k) from the pointer table.  Depends
        only on the assignments — callers remapping several moment slots
        (Adam's m AND v) compute it once and pass it to every
        ``remap_moments`` call."""
        ptr = jnp.asarray(buffers["ptr"])
        return jax.vmap(lambda a: jnp.bincount(a, length=self.k))(ptr).astype(
            jnp.float32
        )

    def remap_moments(self, moments, old_buffers, new_buffers, *,
                      chunk_size=None, counts=None, id_weights=None):
        """Carry per-row optimizer moments (momentum / Adam m, v) through a
        cluster() transition.

        ``moments`` mirrors params ({"tables": (c, 2, k, dsub)}) and
        describes the OLD rows; the transition rewrote both tables and the
        pointer array, so applying them unchanged starves freshly-written
        centroids with stale second moments (the CAFE failure mode).  The
        remap is the moment-space analog of the centroid update: an id's
        virtual moment is its materialized row-sum (main + helper) under
        the OLD pointers, and each new main row j takes the mean over the
        ids assigned to it; the fresh helper table starts at zero moments,
        matching its zero-initialized params.  Streams the vocab in
        ``chunk_size`` slices like ``assign_all``.

        ``id_weights`` (d1,) — typically the observed id histogram — makes
        the per-cluster mean COUNT-WEIGHTED, matching the frequency-weighted
        centroids: a cluster's moment is dominated by the ids that actually
        trained its rows.  Clusters whose ids were never observed (zero
        total weight) fall back to the uniform mean.
        """
        mt = jnp.asarray(moments["tables"])
        new_ptr = jnp.asarray(new_buffers["ptr"])  # (c, d1) assignments
        if counts is None:
            counts = self.assignment_counts(new_buffers)  # (c, k)
        sums = jnp.zeros((self.c, self.k, self.dsub), jnp.float32)
        wsums = jnp.zeros_like(sums)
        wcounts = jnp.zeros((self.c, self.k), jnp.float32)
        def seg(vals, idx):
            return jax.ops.segment_sum(vals, idx, num_segments=self.k)

        for ids in self._id_chunks(chunk_size):
            per_id = self.materialize({"tables": mt}, old_buffers, ids)
            per_id = per_id.astype(jnp.float32)
            idx = new_ptr[:, ids]
            sums = sums + jax.vmap(seg)(per_id, idx)
            if id_weights is not None:
                w = jnp.asarray(id_weights)[ids].astype(jnp.float32)  # (n,)
                wsums = wsums + jax.vmap(seg)(per_id * w[None, :, None], idx)
                wcounts = wcounts + jax.vmap(seg)(jnp.tile(w[None], (self.c, 1)), idx)
        mean = sums / jnp.maximum(counts[..., None], 1.0)
        if id_weights is not None:
            wmean = wsums / jnp.maximum(wcounts[..., None], 1e-12)
            mean = jnp.where(wcounts[..., None] > 0, wmean, mean)
        mean = mean.astype(mt.dtype)
        return {"tables": jnp.stack([mean, jnp.zeros_like(mean)], axis=1)}

    def remap_moments_sharded(self, moments, old_buffers, new_buffers, mesh, *,
                              axis_name: str = DATA_AXIS, chunk_size=None,
                              counts=None, id_weights=None):
        """``remap_moments`` with the vocab sharded over ``axis_name``.

        Both pointer tables enter as id-sharded operands (their local
        tiles align with the shard's contiguous id slice, exactly like
        ``assign_all_sharded``); each shard segment-sums the virtual
        moments of its own ids into (c, k) accumulators and a psum
        assembles the global sums — the (c, k, dsub) result is tiny, the
        (c, d1) tables never leave their shards.  The tail padding is
        MASKED (weight zero), not clamped: a clamped duplicate would be
        COUNTED twice by the segment sums, unlike the row-wise
        assignment pass where duplicates are harmless.  When ``counts``
        is None the per-cluster id counts are accumulated in the same
        pass (masked ones), matching ``assignment_counts`` exactly.  On
        a 1-device axis this reproduces ``remap_moments`` bit-exactly
        (same chunk boundaries, same addition order, identity psums)."""
        from jax.sharding import PartitionSpec as P

        from repro import compat

        nsh = mesh.shape[axis_name]
        d1_pad = ((self.d1 + nsh - 1) // nsh) * nsh
        ids = jnp.minimum(jnp.arange(d1_pad), self.d1 - 1)
        valid = (jnp.arange(d1_pad) < self.d1).astype(jnp.float32)
        old_ptr = self._ptr_padded(old_buffers["ptr"], d1_pad)
        new_ptr = self._ptr_padded(new_buffers["ptr"], d1_pad)
        mt = jnp.asarray(moments["tables"])
        old_hs = jnp.asarray(old_buffers["hs"])
        weighted = id_weights is not None
        w_pad = jnp.zeros(d1_pad, jnp.float32)
        if weighted:
            w_pad = w_pad.at[: self.d1].set(
                jnp.asarray(id_weights).astype(jnp.float32)
            )

        def seg(vals, idx):
            return jax.ops.segment_sum(vals, idx, num_segments=self.k)

        def per_shard(ids_local, valid_local, w_local, old_local, new_local):
            n_local = ids_local.shape[0]
            step = chunk_size if chunk_size and chunk_size < n_local else n_local
            sums = jnp.zeros((self.c, self.k, self.dsub), jnp.float32)
            cnts = jnp.zeros((self.c, self.k), jnp.float32)
            wsums = jnp.zeros_like(sums)
            wcounts = jnp.zeros_like(cnts)
            for s in range(0, n_local, step):
                ids_c = ids_local[s : s + step]
                v = valid_local[s : s + step]
                main = jax.vmap(lambda t, r: t[r])(
                    mt[:, 0], old_local[:, s : s + step]
                )
                helper = jax.vmap(lambda t, r: t[r])(
                    mt[:, 1], self._helper_rows({"hs": old_hs}, ids_c)
                )
                per_id = (main + helper).astype(jnp.float32)
                per_id = per_id * v[None, :, None]
                idx = new_local[:, s : s + step]
                sums = sums + jax.vmap(seg)(per_id, idx)
                cnts = cnts + jax.vmap(seg)(jnp.tile(v[None], (self.c, 1)), idx)
                if weighted:
                    w = w_local[s : s + step] * v
                    wsums = wsums + jax.vmap(seg)(per_id * w[None, :, None], idx)
                    wcounts = wcounts + jax.vmap(seg)(
                        jnp.tile(w[None], (self.c, 1)), idx
                    )
            return jax.lax.psum((sums, cnts, wsums, wcounts), axis_name)

        sums, cnts, wsums, wcounts = compat.shard_map_unchecked(
            per_shard, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(axis_name),
                      P(None, axis_name), P(None, axis_name)),
            out_specs=(P(), P(), P(), P()),
        )(ids, valid, w_pad, old_ptr, new_ptr)
        if counts is None:
            counts = cnts
        mean = sums / jnp.maximum(counts[..., None], 1.0)
        if weighted:
            wmean = wsums / jnp.maximum(wcounts[..., None], 1e-12)
            mean = jnp.where(wcounts[..., None] > 0, wmean, mean)
        mean = mean.astype(mt.dtype)
        return {"tables": jnp.stack([mean, jnp.zeros_like(mean)], axis=1)}

    # --- diagnostics (Appendix H) ----------------------------------------

    def collapse_entropies(self, buffers) -> dict[str, float]:
        """H1 (min column entropy) and H2 (min pairwise entropy) of the
        learned pointer table — the paper's table-collapse detectors.

        H1 near log(k): healthy spread.  H1 near 0: column collapse.
        H2 much below 2*log(k) (and below H1 + log(k)): pairwise collapse
        (one column is a permutation of another).
        """
        ptr = np.asarray(buffers["ptr"])  # (c, d1)
        c = ptr.shape[0]

        def entropy(vals):
            _, counts = np.unique(vals, return_counts=True)
            p = counts / counts.sum()
            return float(-(p * np.log(p)).sum())

        h1 = min(entropy(ptr[i]) for i in range(c))
        h2 = math.inf
        for i in range(c):
            for j in range(i + 1, c):
                pair = ptr[i].astype(np.int64) * (ptr[j].max() + 1) + ptr[j]
                h2 = min(h2, entropy(pair))
        return {"H1": h1, "H2": h2 if c > 1 else float("nan"), "max_H1": math.log(self.k)}

    def sketch_matrix(self, buffers) -> np.ndarray:
        """Dense H (d1, c*2k) for tests: one 1 per (column, table) block."""
        ptr = np.asarray(buffers["ptr"])
        helper = np.asarray(self._helper_rows(buffers, jnp.arange(self.d1)))
        H = np.zeros((self.d1, self.c * 2 * self.k), np.float32)
        rows = np.arange(self.d1)
        for i in range(self.c):
            base = i * 2 * self.k
            H[rows, base + ptr[i]] = 1.0
            H[rows, base + self.k + helper[i]] += 1.0
        return H
