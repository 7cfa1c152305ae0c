"""DLRM (Naumov et al. 2019) — the paper's backbone recommendation model.

13 dense features -> bottom MLP; 26 categorical features -> one embedding
table each (every table independently compressible by any method in the
unified sketch framework, incl. CCE); pairwise dot-product interaction;
top MLP -> 1 logit; Binary Cross-Entropy loss.  Matches the open-source
DLRM benchmark configuration the paper trains on Criteo.

``interaction="dcnv2"`` is MLPerf's DLRM-DCNv2 (torchrec ``DLRM_DCN``;
Wang et al. 2021): the bottom MLP's output beside the flattened
embeddings, ``x0``, goes through ``cross_layers`` low-rank cross layers
``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l`` (V_l to ``cross_rank``
without bias, W_l back with bias) and then the top MLP.  ``bag_sizes``
makes features multi-hot: each feature's embedding is the sum over a bag
of a fixed number of ids (MLPerf's Criteo-1TB multi-hot data).

The 26 tables live behind an ``EmbeddingCollection`` (core/collection.py):
fuse-compatible tables are stacked into grouped supertables and the whole
forward issues O(n_groups) heavy lookups — for the compressed Criteo
config that is ONE universal supertable launch for ALL 26 tables (CCE +
small full tables share the fused Pallas ``cce_lookup``; DESIGN.md §6),
instead of 26 independent gathers.  ``params["emb"]``/``buffers["emb"]``
are in the collection's grouped layout; use
``cfg.collection.feature_params`` / ``feature_buffers`` for a per-feature
view, and ``checkpoint_migrations(cfg)`` to restore pre-collection
checkpoints.  A host-translating pipeline (``data.translate``) may ship
``batch["rows"]`` instead of raw ids — the device then never gathers the
pointer tables.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import embeddings as emb_lib
from repro.core.collection import (
    EmbeddingCollection,
    grouped_layout_migration,
    legacy_layout_migration,
)


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    vocab_sizes: tuple[int, ...]  # one per categorical feature (26 on Criteo)
    n_dense: int = 13
    emb_dim: int = 16
    bottom_mlp: tuple[int, ...] = (512, 256, 64, 16)
    top_mlp: tuple[int, ...] = (512, 256, 1)
    # per-table compression: method + cap on the LARGEST table's params
    emb_method: str = "full"
    emb_param_cap: int = 0  # 0 = uncapped
    emb_c: int = 4
    # CCE transition: what happens to per-row optimizer moments when
    # cluster() rewrites a table ("remap" | "reset" | "keep" — see
    # repro.optim.remap), and the id-chunk size for the full-vocab
    # assignment pass (0 = unchunked; multi-million-row tables should
    # stream so (c, d1, dsub) never materializes at once)
    emb_opt_policy: str = "remap"
    emb_cluster_chunk: int = 1 << 18
    # route grouped CCE lookups through the fused Pallas kernel.  None =
    # auto: Mosaic on TPU, interpret mode on CPU, jnp gather path on GPU
    # (the kernel is TPU-shaped; GPUs have fast native gathers).  CPU
    # interpret mode is SLOWER than the jnp path — it stays the default
    # deliberately so training exercises the exact kernel that ships to
    # TPU (this container's validation contract); set False for CPU speed.
    emb_use_kernel: bool | None = None
    # collection grouping mode: "univ" (universal fusion — ONE heavy
    # launch for the whole embedding stack on the compressed Criteo
    # config), "group" (the pre-universal per-signature grouping) or
    # "loop" (per-feature lookups).  The non-default modes exist as
    # benchmark baselines (bench_kernels --fuse) and escape hatches.
    emb_fuse: str = "univ"
    # model-parallel shard count the supertable codebook axis must divide
    # by: sharded configs set it to the model mesh size (k_pad rounds up;
    # the pad rows are unreachable and stay zero, so layouts with
    # different emb_k_multiple checkpoint-restore into each other
    # bit-exactly — see checkpoint_migrations)
    emb_k_multiple: int = 1
    dtype: Any = jnp.float32
    # ids each feature sum-pools per example; empty = one id per feature.
    # The sparse input is then (B, sum(bag_sizes)), feature f's bag in the
    # columns from sum(bag_sizes[:f]) (see EmbeddingCollection)
    bag_sizes: tuple[int, ...] = ()
    # "dot" (the pairwise dot interaction) or "dcnv2" (the low-rank cross
    # network: cross_layers layers of rank cross_rank)
    interaction: str = "dot"
    cross_layers: int = 0
    cross_rank: int = 0

    def __post_init__(self):
        if self.interaction not in ("dot", "dcnv2"):
            raise ValueError(f"interaction {self.interaction!r}: 'dot' or 'dcnv2'")
        if self.interaction == "dcnv2" and (self.cross_layers < 1 or self.cross_rank < 1):
            raise ValueError("dcnv2 needs cross_layers >= 1 and cross_rank >= 1")

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)

    def _build_table(self, i: int):
        v = self.vocab_sizes[i]
        cap = self.emb_param_cap
        if self.emb_method == "full" or not cap or v * self.emb_dim <= cap:
            # small tables stay uncompressed (paper §Repro: full table for
            # small features, compressed for the big ones)
            return emb_lib.make_table("full", v, self.emb_dim, dtype=self.dtype)
        return emb_lib.make_table(
            self.emb_method, v, self.emb_dim, budget=cap, c=self.emb_c,
            dtype=self.dtype, seed_salt=i,
        )

    @functools.cached_property
    def collection(self) -> EmbeddingCollection:
        """The grouped-table view — built ONCE per config (forward and the
        transition used to reconstruct every table object on every call)."""
        return EmbeddingCollection.build(
            tuple(self._build_table(i) for i in range(self.n_sparse)),
            mode=self.emb_fuse,
            k_multiple=self.emb_k_multiple,
            bag_sizes=self.bag_sizes,
        )

    def table(self, i: int):
        return self.collection.tables[i]

    def n_emb_params(self) -> int:
        return sum(self.table(i).n_params for i in range(self.n_sparse))

    def compression(self) -> float:
        full = sum(v * self.emb_dim for v in self.vocab_sizes)
        return full / max(1, self.n_emb_params())


def _init_mlp(key, sizes: Sequence[int], dtype):
    params = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        k = jax.random.fold_in(key, i)
        params.append({
            "w": (jax.random.normal(k, (a, b)) / math.sqrt(a)).astype(dtype),
            "b": jnp.zeros((b,), dtype),
        })
    return params


def _apply_mlp(params, x, final_act: bool = False):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def _init_cross(key, width: int, rank: int, n_layers: int, dtype):
    """Low-rank cross layers: V (width, rank) without bias, W (rank,
    width) and its bias b, weights normal over the square root of fan-in."""
    layers = []
    for i in range(n_layers):
        kv, kw = jax.random.split(jax.random.fold_in(key, i))
        layers.append({
            "v": (jax.random.normal(kv, (width, rank)) / math.sqrt(width)).astype(dtype),
            "w": (jax.random.normal(kw, (rank, width)) / math.sqrt(rank)).astype(dtype),
            "b": jnp.zeros((width,), dtype),
        })
    return layers


def init(key, cfg: DLRMConfig):
    kb, kt, ke = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "bottom": _init_mlp(kb, (cfg.n_dense, *cfg.bottom_mlp), cfg.dtype),
    }
    buffers: dict[str, Any] = {}
    # grouped layout: one stacked supertable per fuse-compatible group
    # (slices bit-identical to the legacy per-table init)
    params["emb"], buffers["emb"] = cfg.collection.init(ke)
    if cfg.interaction == "dcnv2":
        top_in = cfg.bottom_mlp[-1] + cfg.n_sparse * cfg.emb_dim
        params["cross"] = _init_cross(jax.random.fold_in(key, 3), top_in, cfg.cross_rank,
                                      cfg.cross_layers, cfg.dtype)
    else:
        top_in = cfg.bottom_mlp[-1] + (cfg.n_sparse + 1) * cfg.n_sparse // 2
    params["top"] = _init_mlp(kt, (top_in, *cfg.top_mlp), cfg.dtype)
    return params, buffers


def interact(params, cfg: DLRMConfig, dense, emb):
    """Everything downstream of the embedding lookup: bottom MLP, pairwise
    dot interaction (or the DCNv2 cross network), top MLP -> (B,) logits.

    ``params`` needs only the ``bottom``/``top`` (and ``cross``) subtrees.
    Split out of ``forward`` so the serve engine (serve/dlrm.py) can feed
    embeddings assembled from its hot cache through the identical math — a
    cache hit and a supertable lookup produce bit-identical logits."""
    dense = dense.astype(cfg.dtype)
    x0 = _apply_mlp(params["bottom"], dense, final_act=True)  # (B, emb_dim)
    if cfg.interaction == "dcnv2":
        x0 = jnp.concatenate([x0, emb.astype(cfg.dtype).reshape(x0.shape[0], -1)], axis=-1)
        x = x0
        with jax.named_scope("dcn/cross"):
            for layer in params["cross"]:
                x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
        return _apply_mlp(params["top"], x)[:, 0]
    V = jnp.concatenate([x0[:, None, :], emb.astype(cfg.dtype)], axis=1)
    # pairwise dot interactions (upper triangle, no self)
    inter = jnp.einsum("bie,bje->bij", V, V)
    iu, ju = jnp.triu_indices(V.shape[1], k=1)
    feats = jnp.concatenate([x0, inter[:, iu, ju]], axis=-1)
    return _apply_mlp(params["top"], feats)[:, 0]


def forward(params, buffers, cfg: DLRMConfig, batch, *, mesh=None,
            model_axis=None, batch_axes=None):
    """batch: {"dense": (B, 13) f32, "sparse": (B, 26) int32} -> (B,) logits.

    A host-translating input pipeline (``data.translate``, DESIGN.md §4)
    ships ``batch["rows"]`` — pre-translated codebook row indices —
    instead of (or alongside) ``batch["sparse"]``: the device program
    then never gathers the (c, d1) pointer tables.

    ``mesh``/``model_axis``/``batch_axes`` switch the supertable lookup
    to the model-parallel shard_map path (the slab k-sharded over
    ``model_axis``, ids routed by all-to-all; ``batch_axes`` is the
    FULL batch layout including the model axis —
    ``launch.mesh.all_batch_axes``).  MLPs stay data-parallel under
    jit's normal sharding propagation."""
    use_kernel = cfg.emb_use_kernel
    if use_kernel is None:
        use_kernel = jax.default_backend() in ("tpu", "cpu")
    emb = cfg.collection.lookup_all(
        params["emb"], buffers["emb"], batch.get("sparse"),
        use_kernel=use_kernel, rows=batch.get("rows"),
        mesh=mesh, model_axis=model_axis, batch_axes=batch_axes,
    )  # (B, n_sparse, emb_dim) in O(n_groups) heavy lookups (ONE on Criteo)
    return interact(params, cfg, batch["dense"], emb)


def bce_loss(params, buffers, cfg: DLRMConfig, batch, *, mesh=None,
             model_axis=None, batch_axes=None):
    logits = forward(params, buffers, cfg, batch, mesh=mesh,
                     model_axis=model_axis, batch_axes=batch_axes)
    y = batch["label"].astype(jnp.float32)
    lg = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(lg, 0) - lg * y + jnp.log1p(jnp.exp(-jnp.abs(lg))))


def cluster_tables(key, params, buffers, cfg: DLRMConfig, opt=None, *,
                   id_counts=None, policy: str | None = None,
                   chunk_size: int | None = None,
                   use_kernel: bool | None = None,
                   max_points_per_centroid: int = 256,
                   mesh=None, shard_axis: str | None = None):
    """Run the CCE clustering transition on every CCE table (the training
    callback — Alg. 3 `Cluster`), group-wise through the collection.

    With ``opt`` (the optimizer state, e.g. from ``TrainState.opt``), the
    per-row moments of every transitioned table are carried through the new
    cluster assignments per ``policy`` (default ``cfg.emb_opt_policy``) and
    the updated state is returned as a third element — the 4-arg
    ``Trainer`` cluster protocol.  Without it, returns (params, buffers)
    as before (moments go stale; kept for ablation/legacy callers).

    ``id_counts`` (per-feature histograms, e.g. ``IdFrequencyTracker.counts``)
    runs each table's k-means count-WEIGHTED on the OBSERVED ids — the
    paper's epoch-boundary distribution with zero sampling variance — and
    weights the moment remap the same way.  Without it the sample is
    uniform over the vocab, which on Zipf data lets the never-trained tail
    dominate the centroids.
    """
    from repro.optim.remap import remap_opt_state
    from repro.train.transition import transition_collection

    policy = policy or cfg.emb_opt_policy
    if chunk_size is None:
        chunk_size = cfg.emb_cluster_chunk or None
    new_emb_p, new_emb_b, update_emb = transition_collection(
        cfg.collection, key, params["emb"], buffers["emb"],
        id_counts=id_counts, policy=policy, chunk_size=chunk_size,
        use_kernel=use_kernel, max_points_per_centroid=max_points_per_centroid,
        mesh=mesh, shard_axis=shard_axis,
    )
    new_params = dict(params, emb=new_emb_p)
    new_buffers = dict(buffers, emb=new_emb_b)
    if opt is None:
        return new_params, new_buffers

    def update_moments(moments, _slot):
        return dict(moments, emb=update_emb(moments["emb"]))

    return new_params, new_buffers, remap_opt_state(
        opt, update_moments, policy=policy
    )


def make_id_tracker(cfg: DLRMConfig, stream=None, *, key: str = "sparse"):
    """The frequency tracker the Trainer/transition pair consumes.

    ``stream=None`` returns the DENSE reference tracker (one int64 per
    vocab row — exact, but a second full-vocab array per feature).  A
    ``repro.stream.StreamConfig`` returns the sketch-backed tracker at
    vocab-independent memory, wired through the collection: only the
    features that actually transition (the CCE tables) carry sketches —
    full/loop tables never cluster, so their histograms would be dead
    weight.  Either tracker plugs into ``Trainer(id_tracker=...)`` and
    ``cluster_tables(id_counts=tracker.counts)`` unchanged."""
    from repro.core.cce import CCE
    from repro.stream import IdFrequencyTracker, SketchFrequencyTracker

    if stream is None:
        return IdFrequencyTracker(cfg.vocab_sizes, key=key, bag_sizes=cfg.bag_sizes)
    tracked = tuple(
        i for i, t in enumerate(cfg.collection.tables) if isinstance(t, CCE)
    )
    return SketchFrequencyTracker(
        cfg.vocab_sizes, stream, tracked=tracked, key=key, bag_sizes=cfg.bag_sizes
    )


#: ``k_multiple`` layouts every DLRM trainer can restore checkpoints
#: FROM (and write checkpoints readable BY): 1 covers the 1-device
#: trainer, the powers of two cover the common model-shard counts.  A
#: writer with a k_multiple outside this set needs its own migration.
KNOWN_K_MULTIPLES = (1, 2, 4, 8)


def checkpoint_migrations(cfg: DLRMConfig):
    """``Trainer(migrations=...)`` entries for every older emb layout:
    the pre-collection per-feature layout, the pre-universal grouped
    layout (per-signature CCE slab + full buckets), and every
    ``KNOWN_K_MULTIPLES`` sharded-padding variant of the universal layout
    — all restore bit-exact into this config's supertables (params,
    optimizer moments, buffers, error feedback).  The k_multiple
    migrations are what lets a model-sharded trainer's checkpoint restore
    into a 1-device trainer and vice versa: the extra pad rows are
    unreachable and provably zero, so dropping/adding them through the
    per-feature view loses nothing."""
    migrations = [legacy_layout_migration(cfg.collection)]
    grouped = EmbeddingCollection.build(cfg.collection.tables, mode="group")
    same_layout = tuple((g.kind, g.features) for g in grouped.groups) == tuple(
        (g.kind, g.features) for g in cfg.collection.groups
    )
    if not same_layout:
        migrations.append(
            grouped_layout_migration(cfg.collection, grouped)
        )

    def k_pads(coll):
        return tuple(
            coll.groups[g].k_pad for g in coll.univ_groups
        )

    for m in KNOWN_K_MULTIPLES:
        if m == cfg.emb_k_multiple:
            continue
        other = EmbeddingCollection.build(
            cfg.collection.tables, mode=cfg.emb_fuse, k_multiple=m,
        )
        if k_pads(other) == k_pads(cfg.collection):
            continue  # same padded layout — nothing to migrate
        migrations.append(grouped_layout_migration(cfg.collection, other))
    return migrations
