"""Batched DLRM serving with a SpaceSaving-fed hot-id cache — DESIGN.md §11.

The serve path composes three earned invariants at inference time:

* **One fused launch per serve batch.**  Lookups route through
  ``collection.lookup_all`` with HOST-translated rows
  (``HostTranslator``), so the device program never gathers the pointer
  tables — the same no-ptr-gather contract the training step carries,
  audited by the ``serve_dlrm_cold`` spec.

* **The Zipf head never touches the supertable.**  The SpaceSaving head
  already *names* each feature's hot ids; :class:`HotCache` materializes
  their DECODED embeddings into one small dense device table.  A cache
  hit is a direct gather; the cold tail falls back to the fused launch on
  a COMPACTED sub-batch, with the hit features' rows masked to the ``-1``
  sentinel (a free no-op in the one-hot kernel) so kernel work scales
  with true misses only.  A fully-hit batch skips the launch entirely
  (``serve_dlrm_hit`` audits 0 pallas calls).  Cache answers are
  bit-exact with ``lookup_all`` answers: both are gathers of the same
  decoded rows, and the masked kernel contributes an exact zero.

* **Freshness is enforced, not hoped for.**  The cache records the CCE
  transition epoch of every cached feature at build time; serving across
  a clustering transition without a refresh RAISES
  :class:`StaleCacheError` (silently returning pre-transition rows would
  be a correctness bug, not a performance one).  Refreshes happen at
  transitions (``update_state``), on SpaceSaving head churn
  (``maybe_refresh``, Jaccard distance vs the live tracker export), or
  manually — each one is a ``cache_refresh`` run-log event.

Concurrent user requests aggregate in :class:`MicroBatcher` under a
latency budget: a micro-batch launches when it fills ``max_batch`` or
when the OLDEST request has waited ``latency_budget_s``.  Batches pad to
fixed bucket shapes (default: one batch bucket + one cold bucket = two
compiled programs total); the budget bounds host-side queue wait before
dispatch — NOT device compute, transfer, or cache-refresh pauses.
Per-request latency rides the PR-9 run-log machinery (``request`` events
+ ``LatencyHistogram``).
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embeddings as emb_lib
from repro.data.translate import HostTranslator
from repro.models import dlrm as dlrm_lib
from repro.obs.runlog import LatencyHistogram
from repro.stream.trigger import head_churn


class StaleCacheError(RuntimeError):
    """The hot cache was built against a pre-transition supertable."""


# --- the two compiled programs ----------------------------------------------


def make_serve_fns(cfg, *, use_kernel: bool | None = None):
    """Build the (hit, cold) serve programs for one DLRM config.

    ``hit_fn(mlp_params, cache_tab, slots, dense)`` — fully-cache-hit
    batch: ONE gather of the decoded-embedding cache (slot ``-1`` rows
    contribute zero) feeding the interaction MLPs.  Zero heavy launches;
    takes only the bottom/top MLP params so every input is live.

    ``cold_fn(params, emb_buffers, cache_tab, slots, dense, rows,
    cold_idx)`` — mixed batch: the same cache gather, plus ONE fused
    supertable launch over the compacted cold sub-batch (host-translated
    ``rows``, hit features pre-masked to ``-1`` so the kernel does zero
    work for them and the sum is exactly the cache value), scattered back
    by ``cold_idx`` (pad entries index past the batch and drop).
    ``emb_buffers`` rides along dead — the rows path never reads ptr/hs,
    which is exactly what the ``serve_dlrm_cold`` audit asserts.
    """
    coll = cfg.collection
    if use_kernel is None:
        use_kernel = jax.default_backend() in ("tpu", "cpu")

    def _cache_gather(cache_tab, slots):
        live = (slots >= 0)[..., None].astype(cache_tab.dtype)
        return cache_tab[jnp.maximum(slots, 0)] * live  # (B, F, d2)

    def hit_fn(mlp_params, cache_tab, slots, dense):
        emb = _cache_gather(cache_tab, slots)
        return dlrm_lib.interact(mlp_params, cfg, dense, emb)

    def cold_fn(params, emb_buffers, cache_tab, slots, dense, rows, cold_idx):
        emb = _cache_gather(cache_tab, slots)
        cold = coll.lookup_all(
            params["emb"], emb_buffers, None,
            use_kernel=use_kernel, rows=rows,
        )  # (B_cold, F, d2): ONE fused launch
        emb = emb.at[cold_idx].add(cold.astype(emb.dtype), mode="drop")
        mlp = {"bottom": params["bottom"], "top": params["top"]}
        return dlrm_lib.interact(mlp, cfg, dense, emb)

    return hit_fn, cold_fn


# --- the hot-id cache -------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HotCache:
    """Dense decoded-embedding cache over each feature's hot-id set.

    One concatenated (n_slots, emb_dim) device table; per cached feature
    a SORTED unique id array plus its base offset, so the host-side slot
    lookup is a ``searchsorted`` per feature.  ``epochs`` snapshots the
    CCE transition counter of every cached feature whose buffers carry
    one — the staleness token the engine checks before every batch."""

    ids: dict[int, np.ndarray]  # feature -> sorted unique cached ids
    base: dict[int, int]  # feature -> row offset into `table`
    table: jax.Array  # (max(n_slots, 1), emb_dim) decoded embeddings
    epochs: dict[int, int]  # feature -> transition epoch at build time
    n_slots: int

    @classmethod
    def build(cls, collection, emb_params, emb_buffers,
              head_ids: dict[int, np.ndarray], *, dtype=None) -> "HotCache":
        """Decode ``head_ids[f]`` for every feature through its own table
        (unstacking each touched group ONCE) into the dense cache.  Out
        -of-range / negative ids (empty SpaceSaving slots) are dropped;
        features left with no ids are simply not cached."""
        per_feature: dict[int, np.ndarray] = {}
        for f, ids in head_ids.items():
            t = collection.tables[f]
            ids = np.unique(np.asarray(ids, np.int64))
            ids = ids[(ids >= 0) & (ids < t.d1)].astype(np.int32)
            if ids.size:
                per_feature[f] = ids

        groups_needed = sorted({collection._locate[f][0] for f in per_feature})
        unstacked = {
            g: collection.unstack_group_params(
                collection.groups[g], emb_params[g]
            )
            for g in groups_needed
        }

        base: dict[int, int] = {}
        epochs: dict[int, int] = {}
        chunks = []
        off = 0
        for f in sorted(per_feature):
            g, f_local = collection._locate[f]
            t = collection.tables[f]
            fb = emb_buffers[g][f_local]
            chunks.append(
                t.lookup(unstacked[g][f_local], fb, jnp.asarray(per_feature[f]))
            )
            base[f] = off
            off += per_feature[f].size
            if "epoch" in fb:
                epochs[f] = int(fb["epoch"])
        if chunks:
            table = jnp.concatenate(chunks, axis=0)
            if dtype is not None:
                table = table.astype(dtype)
        else:
            d2 = collection.tables[0].d2 if collection.tables else 1
            table = jnp.zeros((1, d2), dtype or jnp.float32)
        return cls(ids=per_feature, base=base, table=table,
                   epochs=epochs, n_slots=off)

    def slots(self, sparse: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(B, n_features) raw ids -> (slots, hit): cache-table row per
        lookup (``-1`` = miss) and the boolean hit mask.  Features with
        no cached ids miss everywhere."""
        sparse = np.asarray(sparse)
        B, F = sparse.shape
        slots = np.full((B, F), -1, np.int32)
        hit = np.zeros((B, F), bool)
        for f, ids in self.ids.items():
            col = sparse[:, f]
            pos = np.searchsorted(ids, col)
            ok = (pos < ids.size) & (ids[np.minimum(pos, ids.size - 1)] == col)
            slots[ok, f] = self.base[f] + pos[ok]
            hit[:, f] = ok
        return slots, hit

    def check_fresh(self, collection, emb_buffers) -> None:
        """Raise :class:`StaleCacheError` if any cached feature has
        transitioned since the cache was built."""
        for f, ep in self.epochs.items():
            live = int(collection.feature_buffers(emb_buffers, f)["epoch"])
            if live != ep:
                raise StaleCacheError(
                    f"hot cache built at epoch {ep} for feature {f}, "
                    f"supertable is at epoch {live}; refresh the cache "
                    "(DLRMServeEngine.update_state) before serving"
                )


# --- request aggregation ----------------------------------------------------


@dataclasses.dataclass
class ServeRequest:
    uid: int
    dense: np.ndarray  # (n_dense,)
    sparse: np.ndarray  # (n_sparse,) raw ids
    t_arrival: float | None = None


@dataclasses.dataclass(frozen=True)
class ServeResult:
    uid: int
    logit: float
    latency_s: float
    cache_hit: bool  # every feature answered from the hot cache


class MicroBatcher:
    """Aggregate concurrent requests into fixed-shape micro-batches.

    A batch is ready when ``max_batch`` requests are pending or the
    OLDEST pending request has waited ``latency_budget_s`` — the budget
    bounds queue wait before dispatch, nothing downstream of it.  The
    clock is injectable so tests drive time deterministically."""

    def __init__(self, *, max_batch: int, latency_budget_s: float = 2e-3,
                 clock=time.monotonic):
        self.max_batch = int(max_batch)
        self.latency_budget_s = float(latency_budget_s)
        self.clock = clock
        self._pending: collections.deque[ServeRequest] = collections.deque()

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, req: ServeRequest) -> None:
        if req.t_arrival is None:
            req.t_arrival = self.clock()
        self._pending.append(req)

    def ready(self) -> bool:
        if len(self._pending) >= self.max_batch:
            return True
        if not self._pending:
            return False
        waited = self.clock() - self._pending[0].t_arrival
        return waited >= self.latency_budget_s

    def take(self) -> list[ServeRequest]:
        return [
            self._pending.popleft()
            for _ in range(min(self.max_batch, len(self._pending)))
        ]


def _pick_bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


# --- the engine -------------------------------------------------------------


class DLRMServeEngine:
    """Batched DLRM inference over the fused supertable + hot-id cache.

    ``tracker`` (a ``SketchFrequencyTracker``) feeds the cache: its
    SpaceSaving heads name the hot ids per CCE feature, and small full
    tables (``d1 <= full_cache_max``) are cached whole.  ``cache=False``
    disables the cache entirely (every batch takes the cold path — the
    bench baseline).  Shapes are bucketed: ``batch_buckets`` /
    ``cold_buckets`` default to ``(max_batch,)`` so the engine compiles
    exactly two programs; finer cold buckets trade extra compiles for
    less padded kernel work on sparse-miss traffic."""

    def __init__(self, params, buffers, cfg, *, tracker=None, cache=True,
                 max_batch: int = 8, latency_budget_s: float = 2e-3,
                 batch_buckets: tuple[int, ...] | None = None,
                 cold_buckets: tuple[int, ...] | None = None,
                 head_n: int | None = None, full_cache_max: int = 8192,
                 churn_threshold: float = 0.5,
                 use_kernel: bool | None = None, run_log=None,
                 clock=time.monotonic):
        coll = cfg.collection
        if coll.multi_hot:
            raise ValueError(
                f"DLRMServeEngine serves one id a feature; this config has bag_sizes "
                f"{coll.bag_sizes} (multi-hot bags are looked up in training only)")
        unfused = sorted({g.kind for g in coll.groups if g.kind != "univ"})
        if unfused:
            raise ValueError(
                "DLRMServeEngine serves host-translated rows, which cover "
                f"universal groups only; this collection has {unfused} "
                "groups (build the config with emb_fuse='univ')"
            )
        self.cfg = cfg
        self.params = params
        self.buffers = buffers
        self.tracker = tracker
        self.run_log = run_log
        self.clock = clock
        self.head_n = head_n
        self.full_cache_max = int(full_cache_max)
        self.churn_threshold = float(churn_threshold)
        self.max_batch = int(max_batch)
        self.batch_buckets = tuple(sorted(batch_buckets or (max_batch,)))
        self.cold_buckets = tuple(sorted(cold_buckets or (max_batch,)))
        if self.batch_buckets[-1] < max_batch:
            raise ValueError("batch_buckets must cover max_batch")

        hit_fn, cold_fn = make_serve_fns(cfg, use_kernel=use_kernel)
        self._hit = jax.jit(hit_fn)
        self._cold = jax.jit(cold_fn)
        self._mlp_params = {"bottom": params["bottom"], "top": params["top"]}
        self.translator = HostTranslator(coll, buffers["emb"])
        self._live_epochs = self._read_epochs(buffers["emb"])

        self.batcher = MicroBatcher(max_batch=max_batch,
                                    latency_budget_s=latency_budget_s,
                                    clock=clock)
        self.hist = LatencyHistogram()
        self.hist_hit = LatencyHistogram()
        self.hist_cold = LatencyHistogram()
        self.counters = collections.Counter()

        self.cache: HotCache | None = None
        self._use_cache = bool(cache)
        if self._use_cache:
            self.refresh_cache(reason="init")

    # --- cache lifecycle --------------------------------------------------

    def _read_epochs(self, emb_buffers) -> dict[int, int]:
        coll = self.cfg.collection
        out = {}
        for f in range(self.cfg.n_sparse):
            fb = coll.feature_buffers(emb_buffers, f)
            if "epoch" in fb:
                out[f] = int(fb["epoch"])
        return out

    def _head_ids(self) -> dict[int, np.ndarray]:
        """Cache coverage: SpaceSaving heads for tracked (CCE) features,
        whole tables for full tables small enough to hold outright."""
        out: dict[int, np.ndarray] = {}
        coll = self.cfg.collection
        for f, t in enumerate(coll.tables):
            if isinstance(t, emb_lib.FullTable) and t.d1 <= self.full_cache_max:
                out[f] = np.arange(t.d1, dtype=np.int32)
        if self.tracker is not None:
            for f, ids in self.tracker.export_heads(self.head_n).items():
                if f not in out:
                    out[f] = ids
        return out

    def refresh_cache(self, *, reason: str = "manual",
                      churn: float | None = None) -> HotCache:
        """(Re)build the hot cache from the live params/buffers + tracker
        heads; logs a ``cache_refresh`` run-log event."""
        self._use_cache = True
        self.cache = HotCache.build(
            self.cfg.collection, self.params["emb"], self.buffers["emb"],
            self._head_ids(),
        )
        self.counters["n_refreshes"] += 1
        if self.run_log is not None:
            fields = dict(reason=reason, n_slots=self.cache.n_slots,
                          n_features=len(self.cache.ids))
            if churn is not None:
                fields["churn"] = float(churn)
            self.run_log.append("cache_refresh", dedupe=False, **fields)
        return self.cache

    def update_state(self, params, buffers, *, refresh_cache: bool = True):
        """Point the engine at post-transition params/buffers: re-syncs
        the host translator and (by default) rebuilds the cache.  With
        ``refresh_cache=False`` the stale cache is KEPT — the next served
        batch raises :class:`StaleCacheError` (tested), because the live
        epochs advance here while the cache's snapshot does not."""
        self.params = params
        self.buffers = buffers
        self._mlp_params = {"bottom": params["bottom"], "top": params["top"]}
        self.translator.update(buffers["emb"])
        self._live_epochs = self._read_epochs(buffers["emb"])
        if refresh_cache and self._use_cache:
            self.refresh_cache(reason="transition")

    def maybe_refresh(self) -> float | None:
        """Poll head churn: Jaccard distance between each cached head and
        the tracker's CURRENT head, refresh at ``churn_threshold``.
        Returns the max churn observed (None without tracker+cache)."""
        if self.tracker is None or self.cache is None:
            return None
        fresh = self.tracker.export_heads(self.head_n)
        churns = [
            head_churn(self.cache.ids[f], fresh[f])
            for f in self.cache.ids
            if f in fresh
        ]
        if not churns:
            return None
        churn = max(churns)
        if churn >= self.churn_threshold:
            self.refresh_cache(reason="head-churn", churn=churn)
        return churn

    # --- serving ----------------------------------------------------------

    def predict(self, dense: np.ndarray, sparse: np.ndarray) -> np.ndarray:
        """Synchronous batch inference (tests / bench): (B, n_dense) f32 +
        (B, n_sparse) ids -> (B,) logits, through the same bucketed
        hit/cold programs the request path uses."""
        logits, _ = self._serve_batch(np.asarray(dense), np.asarray(sparse))
        return logits

    def submit(self, req: ServeRequest) -> None:
        self.batcher.submit(req)

    def step(self) -> list[ServeResult]:
        """Serve ONE micro-batch if the batcher is ready (full, or the
        oldest request exceeded the latency budget)."""
        if not self.batcher.ready():
            return []
        return self._run(self.batcher.take())

    def drain(self) -> list[ServeResult]:
        """Serve everything pending regardless of the budget."""
        out = []
        while len(self.batcher):
            out.extend(self._run(self.batcher.take()))
        return out

    def _run(self, reqs: list[ServeRequest]) -> list[ServeResult]:
        dense = np.stack([r.dense for r in reqs]).astype(np.float32)
        sparse = np.stack([r.sparse for r in reqs]).astype(np.int64)
        logits, elem_hit = self._serve_batch(dense, sparse)
        t_done = self.clock()
        results = []
        for i, r in enumerate(reqs):
            lat = t_done - (r.t_arrival if r.t_arrival is not None else t_done)
            hit = bool(elem_hit[i])
            results.append(ServeResult(uid=r.uid, logit=float(logits[i]),
                                       latency_s=lat, cache_hit=hit))
            self.hist.observe(lat)
            (self.hist_hit if hit else self.hist_cold).observe(lat)
            self.counters["n_requests"] += 1
            self.counters["n_hit_requests"] += int(hit)
            if self.run_log is not None:
                self.run_log.append("request", dedupe=False, uid=r.uid,
                                    latency_s=lat, cache_hit=hit)
        return results

    def _serve_batch(self, dense, sparse) -> tuple[np.ndarray, np.ndarray]:
        """The two-program core: cache slots on host, compact the cold
        tail, ONE fused launch iff it is non-empty."""
        cache = self.cache
        if cache is not None and cache.epochs != {
            f: self._live_epochs[f] for f in cache.epochs
        }:
            stale = [f for f, ep in cache.epochs.items()
                     if self._live_epochs.get(f) != ep]
            raise StaleCacheError(
                f"hot cache is stale for features {stale}: the supertable "
                "transitioned since the last refresh; call update_state() "
                "or refresh_cache() before serving"
            )
        n_real, F = sparse.shape[0], self.cfg.n_sparse
        if self.tracker is not None and n_real:
            self.tracker.observe({self.tracker.key: sparse})
        B = _pick_bucket(n_real, self.batch_buckets)
        dense_p = np.zeros((B, dense.shape[1]), np.float32)
        dense_p[:n_real] = dense
        if cache is not None and cache.n_slots:
            slots, hit = cache.slots(sparse)
            cache_tab = cache.table
        else:
            slots = np.full((n_real, F), -1, np.int32)
            hit = np.zeros((n_real, F), bool)
            cache_tab = self._empty_tab()
        # pad elements are fully "hit": slot -1 gathers zero, no cold work
        slots_p = np.full((B, F), -1, np.int32)
        slots_p[:n_real] = slots
        hit_p = np.ones((B, F), bool)
        hit_p[:n_real] = hit
        elem_hit = hit.all(axis=1) if n_real else np.zeros((0,), bool)

        self.counters["n_batches"] += 1
        self.counters["n_id_lookups"] += int(n_real) * F
        self.counters["n_id_hits"] += int(hit.sum())  # audit: allow-int-cast

        cold = np.flatnonzero(~hit_p.all(axis=1))
        if cold.size == 0:
            self.counters["n_hit_batches"] += 1
            out = self._hit(self._mlp_params, cache_tab,
                            jnp.asarray(slots_p), jnp.asarray(dense_p))
        else:
            self.counters["n_cold_batches"] += 1
            self.counters["n_launches"] += 1
            Bc = _pick_bucket(cold.size, self.cold_buckets)
            coll = self.cfg.collection
            rows = self.translator.rows_masked(sparse[cold], hit[cold])
            rows_p = np.full(
                (Bc, coll.rows_n_cols, coll.rows_n_tables), -1, np.int32
            )
            rows_p[: cold.size] = rows
            # pad entries index past the batch: dropped by mode="drop"
            # (never -1 — negative indices WRAP in jax scatters)
            cold_idx = np.full((Bc,), B, np.int32)
            cold_idx[: cold.size] = cold
            out = self._cold(self.params, self.buffers["emb"], cache_tab,
                             jnp.asarray(slots_p), jnp.asarray(dense_p),
                             jnp.asarray(rows_p), jnp.asarray(cold_idx))
        return np.asarray(out)[:n_real], elem_hit

    def _empty_tab(self):
        if not hasattr(self, "_empty_tab_cached"):
            self._empty_tab_cached = jnp.zeros(
                (1, self.cfg.emb_dim), self.cfg.dtype
            )
        return self._empty_tab_cached

    # --- stats ------------------------------------------------------------

    def flush_stats(self) -> dict:
        """Summary rates + (when a run log is attached) three labeled
        ``latency_hist`` events: overall / cache-hit / cold."""
        c = self.counters
        out = {
            "n_requests": int(c["n_requests"]),
            "n_batches": int(c["n_batches"]),
            "n_launches": int(c["n_launches"]),
            "n_refreshes": int(c["n_refreshes"]),
            "hit_rate_requests": (
                c["n_hit_requests"] / c["n_requests"] if c["n_requests"] else 0.0
            ),
            "hit_rate_ids": (
                c["n_id_hits"] / c["n_id_lookups"] if c["n_id_lookups"] else 0.0
            ),
            "launches_per_batch": (
                c["n_launches"] / c["n_batches"] if c["n_batches"] else 0.0
            ),
        }
        if self.run_log is not None:
            for hist, label in ((self.hist, "serve-dlrm"),
                                (self.hist_hit, "serve-dlrm-hit"),
                                (self.hist_cold, "serve-dlrm-cold")):
                if hist.n:
                    self.run_log.append(
                        "latency_hist", dedupe=False,
                        **(hist.to_dict() | {"label": label}),
                    )
        return out
