"""Kernel microbenchmarks: the fused CCE lookup and kmeans-assign kernels
vs their pure-jnp references (CPU interpret mode — wall times here are NOT
TPU times; the structural claim is identical results + the blocked
structure; the roofline for the kernels is derived analytically below).

Emits CSV rows: name,us_per_call,bytes_model,flops_model.

``--collection`` (or a plain ``python benchmarks/bench_kernels.py`` run)
additionally benches the EmbeddingCollection refactor end-to-end: a
26-feature DLRM embedding step, legacy per-feature loop vs grouped
supertables, launches-per-step counted, results written to
``BENCH_collection.json`` (uploaded as a CI artifact).

``--stream`` benches the streaming-statistics subsystem: dense vs sketch
frequency tracker memory (at the real Criteo vocabularies) and observe()
throughput (sync conservative vs async device path), written to
``BENCH_stream.json`` (also a CI artifact).

``--fuse`` benches the launch-fusion trajectory (DESIGN.md §6): the same
26-feature DLRM embedding step under the per-feature loop, the PR-3
3-group collection, and the unified single-launch supertable (plus the
host-translated-rows variant) — launches/step and emb fwd+bwd latency,
written to ``BENCH_fuse.json`` (also a CI artifact).

``--shard`` compares the replicated vs model-sharded DLRM train step at
the FULL Criteo vocabularies, AOT only (abstract lower + compile — zero
array allocation, so the 12.8 GB replicated state never exists): pallas
launches, per-kind collective counts and ICI/DCN bytes from
``repro.launch.hlo_cost``, and per-device state bytes (supertable slab,
optimizer moments, pointer tables) from the step's own output shardings,
written to ``BENCH_shard.json`` (also a CI artifact).  Needs a
multi-device runtime; the CLI re-execs itself under a forced 4-device
CPU when launched on one device.

``--obs`` benches the in-step telemetry's overhead on the reduced DLRM
step — off, on, and on with the async metrics pump draining — written
to ``BENCH_obs.json`` (also a CI artifact; the claim is <= 2%).

``--serve`` benches the DLRM serve engine (DESIGN.md §11) under
synthetic Zipf(1.0) traffic at a 10M-id space: per-request p50/p99
latency for head traffic (fully cache-hit, launch-free), mixed Zipf
traffic, and a cache-disabled baseline (every batch pays the fused
launch), plus cache-hit rates and launches per batch — written to
``BENCH_serve.json`` with the per-request run log in
``BENCH_serve_run.jsonl`` (both CI artifacts).
"""
import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def timeit(fn, *args, reps=5):
    fn(*args)  # warmup/compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def main(out=print):
    key = jax.random.PRNGKey(0)
    rows = []

    # CCE lookup at a DLRM-ish shape
    c, B, T, k, dsub = 4, 4096, 2, 2048, 16
    idx = jax.random.randint(key, (c, B, T), 0, k)
    tables = jax.random.normal(key, (c, T, k, dsub), jnp.float32)
    t_ref = timeit(jax.jit(ref.cce_lookup_ref), idx, tables)
    t_ker = timeit(jax.jit(ops.cce_lookup), idx, tables)
    # TPU-model traffic: tables tiles (c*T*k*dsub) + out (B*c*dsub), f32
    bytes_model = 4 * (c * T * k * dsub + B * c * dsub + c * B * T)
    flops_model = 2 * c * T * B * dsub  # gather-as-matmul useful adds
    rows.append(("cce_lookup_ref", t_ref, bytes_model, flops_model))
    rows.append(("cce_lookup_kernel_interp", t_ker, bytes_model, flops_model))

    # kmeans assign at clustering scale
    n, kc, d = 4096, 512, 16
    x = jax.random.normal(key, (n, d), jnp.float32)
    cen = jax.random.normal(jax.random.fold_in(key, 1), (kc, d), jnp.float32)
    t_ref = timeit(jax.jit(ref.kmeans_assign_ref), x, cen)
    t_ker = timeit(jax.jit(ops.kmeans_assign), x, cen)
    bytes_model = 4 * (n * d + kc * d + n)
    flops_model = 2 * n * kc * d
    rows.append(("kmeans_assign_ref", t_ref, bytes_model, flops_model))
    rows.append(("kmeans_assign_kernel_interp", t_ker, bytes_model, flops_model))

    # the transition's full-vocab assignment pass (CCE.assign_all): one
    # chunked materialization, per-column assign via the jnp path vs the
    # Pallas kernel route
    from repro.core.cce import CCE

    cce = CCE(d1=8192, d2=64, k=256, c=4)
    cparams, cbuffers = cce.init(key)
    cents = jax.random.normal(
        jax.random.fold_in(key, 2), (cce.c, cce.k, cce.dsub), jnp.float32
    )
    t_jnp = timeit(
        jax.jit(lambda p, b, c: cce.assign_all(p, b, c, chunk_size=2048,
                                               use_kernel=False)),
        cparams, cbuffers, cents,
    )
    t_ker = timeit(
        jax.jit(lambda p, b, c: cce.assign_all(p, b, c, chunk_size=2048,
                                               use_kernel=True)),
        cparams, cbuffers, cents,
    )
    bytes_model = 4 * (cce.c * cce.d1 * cce.dsub + cce.c * cce.k * cce.dsub
                       + cce.c * cce.d1)
    flops_model = 2 * cce.c * cce.d1 * cce.k * cce.dsub
    rows.append(("cce_assign_all_jnp", t_jnp, bytes_model, flops_model))
    rows.append(("cce_assign_all_kernel_interp", t_ker, bytes_model, flops_model))

    out("name,us_per_call,bytes_model,flops_model")
    for r in rows:
        out(f"{r[0]},{r[1]:.0f},{r[2]},{r[3]}")
    return rows


def bench_collection(out=print, json_path="BENCH_collection.json",
                     batch=256, reps=3):
    """Looped vs fused DLRM embedding step (the PR's structural claim).

    A 26-feature DLRM at Criteo-shaped (CI-capped) vocabs; measures the
    embedding forward+backward and the full DLRM loss step under (a) the
    legacy per-feature lookup loop and (b) the grouped collection —
    fused-jnp and fused-kernel variants — and counts heavy lookup
    launches per step (n_features -> n_groups).  On CPU the kernel runs
    in interpret mode, so its WALL TIME is not meaningful off-TPU; the
    launch counts and the looped-vs-fused-jnp times are.
    """
    import dataclasses

    import numpy as np

    from repro.configs import dlrm_criteo
    from repro.models import dlrm
    from repro.models.dlrm import DLRMConfig

    vocabs = tuple(min(v, 20_000) for v in dlrm_criteo.CRITEO_KAGGLE_VOCABS)
    cfg = DLRMConfig(
        vocab_sizes=vocabs, n_dense=13, emb_dim=16,
        bottom_mlp=(64, 32, 16), top_mlp=(64, 1),
        emb_method="cce", emb_param_cap=2048,
    )
    coll = cfg.collection
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch_tree = {
        "dense": jnp.asarray(rng.normal(size=(batch, 13)), jnp.float32),
        "sparse": jnp.asarray(
            np.stack([rng.integers(0, v, batch) for v in vocabs], axis=1),
            jnp.int32,
        ),
        "label": jnp.asarray(rng.integers(0, 2, batch), jnp.float32),
    }
    sparse = batch_tree["sparse"]
    co = jax.random.normal(jax.random.PRNGKey(1), (batch, cfg.n_sparse, 16))
    per_p = jax.tree.map(jnp.asarray, coll.unstack_params(params["emb"]))
    per_b = coll.unstack_buffers(buffers["emb"])

    def emb_looped(pp):  # the pre-collection hot loop: 26 lookups
        outv = jnp.stack(
            [
                coll.tables[i].lookup(pp[i], per_b[i], sparse[:, i])
                for i in range(coll.n_features)
            ],
            axis=1,
        )
        return jnp.sum(outv * co)

    def emb_fused(ep, use_kernel):
        outv = coll.lookup_all(ep, buffers["emb"], sparse, use_kernel=use_kernel)
        return jnp.sum(outv * co)

    t_loop = timeit(jax.jit(jax.grad(emb_looped)), per_p, reps=reps)
    t_jnp = timeit(
        jax.jit(jax.grad(lambda ep: emb_fused(ep, False))), params["emb"], reps=reps
    )
    t_ker = timeit(
        jax.jit(jax.grad(lambda ep: emb_fused(ep, True))), params["emb"], reps=reps
    )

    def e2e_fused(p):
        return dlrm.bce_loss(
            p, buffers, dataclasses.replace(cfg, emb_use_kernel=False), batch_tree
        )

    def e2e_looped(p):
        # the pre-collection dlrm.forward: per-feature lookups spliced into
        # the same interaction + MLP stack
        x0 = batch_tree["dense"]
        for i, layer in enumerate(p["bottom"]):
            x0 = x0 @ layer["w"] + layer["b"]
            x0 = jax.nn.relu(x0)
        vecs = [x0] + [
            coll.tables[i].lookup(p["emb"][i], per_b[i], sparse[:, i])
            for i in range(coll.n_features)
        ]
        V = jnp.stack(vecs, axis=1)
        inter = jnp.einsum("bie,bje->bij", V, V)
        iu, ju = jnp.triu_indices(V.shape[1], k=1)
        feats = jnp.concatenate([x0, inter[:, iu, ju]], axis=-1)
        x = feats
        for i, layer in enumerate(p["top"]):
            x = x @ layer["w"] + layer["b"]
            if i < len(p["top"]) - 1:
                x = jax.nn.relu(x)
        lg = x[:, 0]
        y = batch_tree["label"]
        return jnp.mean(
            jnp.maximum(lg, 0) - lg * y + jnp.log1p(jnp.exp(-jnp.abs(lg)))
        )

    t_e2e_fused = timeit(jax.jit(jax.grad(e2e_fused)), params, reps=reps)
    params_loop = dict(params, emb=per_p)
    t_e2e_loop = timeit(jax.jit(jax.grad(e2e_looped)), params_loop, reps=reps)

    result = {
        "backend": jax.default_backend(),
        "note": "CPU kernel times are interpret-mode (validation), not TPU",
        "batch": batch,
        "n_features": coll.n_features,
        "n_groups": coll.n_groups,
        "launches_per_step": {"looped": coll.n_features,
                              "fused": coll.n_lookup_launches},
        "groups": [
            {"kind": g.kind, "features": list(g.features)} for g in coll.groups
        ],
        "emb_fwd_bwd_us": {"looped": t_loop, "fused_jnp": t_jnp,
                           "fused_kernel_interp": t_ker},
        "e2e_dlrm_step_us": {"looped": t_e2e_loop, "fused_jnp": t_e2e_fused},
    }
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out("collection: " + json.dumps(result["launches_per_step"]))
    out(f"emb fwd+bwd us: looped={t_loop:.0f} fused_jnp={t_jnp:.0f} "
        f"fused_kernel_interp={t_ker:.0f}")
    out(f"e2e dlrm step us: looped={t_e2e_loop:.0f} fused_jnp={t_e2e_fused:.0f}")
    out(f"wrote {json_path}")
    return result


def bench_fuse(out=print, json_path="BENCH_fuse.json", batch=256, reps=3):
    """Looped vs 3-group vs unified embedding step (the launch-fusion
    trajectory, DESIGN.md §6).

    The same Criteo-shaped (CI-capped) 26-feature DLRM tables run under
    all three collection modes; per mode the embedding forward+backward is
    timed on the fused-jnp path (meaningful on CPU; the kernel path is
    interpret mode off-TPU and is timed separately for the fused modes as
    a structural check only) and the heavy launch count is recorded.  A
    fourth variant feeds HOST-translated rows to the unified collection —
    the pod-scale dataflow where the device never gathers the pointer
    tables.
    """
    import numpy as np

    from repro.configs import dlrm_criteo
    from repro.core.collection import EmbeddingCollection
    from repro.data import HostTranslator
    from repro.models.dlrm import DLRMConfig

    vocabs = tuple(min(v, 20_000) for v in dlrm_criteo.CRITEO_KAGGLE_VOCABS)
    cfg = DLRMConfig(
        vocab_sizes=vocabs, n_dense=13, emb_dim=16,
        bottom_mlp=(64, 32, 16), top_mlp=(64, 1),
        emb_method="cce", emb_param_cap=2048,
    )
    tables = cfg.collection.tables
    rng = np.random.default_rng(0)
    sparse_np = np.stack(
        [rng.integers(0, v, batch) for v in vocabs], axis=1
    ).astype(np.int32)
    sparse = jnp.asarray(sparse_np)
    co = jax.random.normal(jax.random.PRNGKey(1), (batch, cfg.n_sparse, 16))
    key = jax.random.PRNGKey(0)

    modes = {"looped": "loop", "grouped3": "group", "unified": "univ"}
    launches, times = {}, {}
    univ = None
    for name, mode in modes.items():
        coll = EmbeddingCollection.build(tables, mode=mode)
        params, buffers = coll.init(key)
        launches[name] = coll.n_lookup_launches

        def emb_loss(p, uk, _coll=coll, _buf=buffers):
            outv = _coll.lookup_all(p, _buf, sparse, use_kernel=uk)
            return jnp.sum(outv * co)

        times[name] = {
            "fused_jnp": timeit(
                jax.jit(jax.grad(lambda p: emb_loss(p, False))), params,
                reps=reps,
            )
        }
        if mode != "loop":  # structural check only off-TPU (interpret)
            times[name]["kernel_interp"] = timeit(
                jax.jit(jax.grad(lambda p: emb_loss(p, True))), params,
                reps=reps,
            )
        if mode == "univ":
            univ = (coll, params, buffers)

    # unified + host-translated rows: the device program consumes only
    # the pre-translated (B, cols, T) tensor
    coll, params, buffers = univ
    translator = HostTranslator(coll, buffers)
    t0 = time.perf_counter()
    rows_np = translator.rows(sparse_np)
    translate_us = (time.perf_counter() - t0) * 1e6
    rows = jnp.asarray(rows_np)

    def emb_loss_rows(p):
        outv = coll.lookup_all(p, buffers, None, use_kernel=False, rows=rows)
        return jnp.sum(outv * co)

    times["unified_host_rows"] = {
        "fused_jnp": timeit(jax.jit(jax.grad(emb_loss_rows)), params, reps=reps),
        "host_translate_us": translate_us,
    }
    launches["unified_host_rows"] = launches["unified"]

    result = {
        "backend": jax.default_backend(),
        "note": ("CPU kernel times are interpret-mode (validation), not "
                 "TPU; the structural claim is launches/step"),
        "batch": batch,
        "n_features": cfg.n_sparse,
        "launches_per_step": launches,
        "emb_fwd_bwd_us": times,
        "rows_tensor": {"cols": coll.rows_n_cols, "T": coll.rows_n_tables},
    }
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out("fuse: launches/step " + json.dumps(launches))
    out("emb fwd+bwd us (fused_jnp): " + json.dumps(
        {k: round(v["fused_jnp"]) for k, v in times.items()}))
    out(f"wrote {json_path}")
    return result


def bench_stream(out=print, json_path="BENCH_stream.json",
                 batch=4096, n_batches=32):
    """Dense vs sketch frequency tracker: state memory and observe()
    throughput (the streaming-statistics subsystem's structural claim —
    DESIGN.md §5).

    Memory is measured at the REAL Criteo vocabularies (the dense
    tracker's cost is what it would be in production; its arrays are
    lazily-zero so allocating them is safe to measure, the sketch is
    measured live).  Throughput runs on a capped-vocab Zipf stream: dense
    ``np.add.at`` vs sketch conservative update vs the async device path
    (jitted segment-sum + background fold — the number that matters is
    the HOT-PATH cost, i.e. how long ``observe`` blocks the step loop;
    the fold drains off-thread and is charged separately via flush).
    """
    import dataclasses

    import numpy as np

    from repro.configs import dlrm_criteo
    from repro.models import dlrm
    from repro.models.dlrm import DLRMConfig
    from repro.stream import IdFrequencyTracker

    # --- memory at full Criteo scale (no data needed; async_fold off —
    # the tracker is read once for nbytes, no folder thread/jit needed) --
    full_cfg = dlrm_criteo.CONFIG
    sketch_full = dlrm.make_id_tracker(
        full_cfg, dataclasses.replace(dlrm_criteo.STREAM, async_fold=False)
    )
    dense_bytes = sum(v * 8 for v in full_cfg.vocab_sizes)  # int64 per row
    mem = {
        "vocab_rows": int(sum(full_cfg.vocab_sizes)),
        "dense_bytes": dense_bytes,
        "sketch_bytes": int(sketch_full.nbytes),
        "ratio": dense_bytes / max(1, sketch_full.nbytes),
        "stream_config": dataclasses.asdict(dlrm_criteo.STREAM),
    }

    # --- update throughput on a Zipf stream (capped vocabs) ---------------
    vocabs = tuple(min(v, 100_000) for v in dlrm_criteo.CRITEO_KAGGLE_VOCABS)
    cfg = DLRMConfig(vocab_sizes=vocabs, emb_method="cce", emb_param_cap=2048)
    rng = np.random.default_rng(0)
    batches = [
        {"sparse": np.stack(
            [rng.zipf(1.2, batch) % v for v in vocabs], axis=1
        ).astype(np.int64)}
        for _ in range(n_batches)
    ]

    def run(tracker):
        tracker.observe(batches[0])  # warm (jit compile on the async path)
        getattr(tracker, "flush", lambda: None)()
        t0 = time.perf_counter()
        for b in batches:
            tracker.observe(b)
        hot = time.perf_counter() - t0
        getattr(tracker, "flush", lambda: None)()
        return hot, time.perf_counter() - t0

    stream_cfg = dlrm_criteo.reduced_stream(window=0)
    hot_dense, _ = run(IdFrequencyTracker(vocabs))
    hot_sketch, _ = run(dlrm.make_id_tracker(cfg, stream_cfg))
    hot_async, total_async = run(
        dlrm.make_id_tracker(
            cfg, dataclasses.replace(stream_cfg, async_fold=True)
        )
    )
    # the async design's structural claim: the hot path is ONE jitted
    # dispatch + an enqueue.  Measure the dispatch alone (few in flight,
    # so the device queue never backs up) — on a real accelerator this is
    # the whole hot-path cost; on CPU the "device" is the host, so the
    # sustained async numbers above contend with the fold thread for the
    # same cores and understate the design.
    async_tr = dlrm.make_id_tracker(
        cfg, dataclasses.replace(stream_cfg, async_fold=True)
    )
    cols = np.ascontiguousarray(
        batches[0]["sparse"][:, list(async_tr.tracked)]
    )
    jcols = jnp.asarray(cols, jnp.int32)
    jax.block_until_ready(async_tr._cell_counter(jcols))
    t0 = time.perf_counter()
    for _ in range(8):
        async_tr._cell_counter(jcols)
    dispatch_us = (time.perf_counter() - t0) / 8 * 1e6

    ids_per_batch = batch * len(vocabs)
    thr = {
        "batch": batch,
        "n_features": len(vocabs),
        "ids_per_batch": ids_per_batch,
        "observe_us_per_batch": {
            "dense": hot_dense / n_batches * 1e6,
            "sketch_sync": hot_sketch / n_batches * 1e6,
            "sketch_async_hot_path": hot_async / n_batches * 1e6,
            "sketch_async_with_fold": total_async / n_batches * 1e6,
            "async_dispatch_only": dispatch_us,
        },
        "ids_per_sec_hot_path": {
            "dense": ids_per_batch * n_batches / hot_dense,
            "sketch_sync": ids_per_batch * n_batches / hot_sketch,
            "sketch_async": ids_per_batch * n_batches / hot_async,
        },
    }
    result = {
        "backend": jax.default_backend(),
        "note": ("on CPU the 'device' is the host: sustained async numbers "
                 "contend with the fold thread for the same cores; "
                 "async_dispatch_only is the structural hot-path cost"),
        "memory": mem,
        "throughput": thr,
    }
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out(f"memory: dense {dense_bytes / 1e6:.0f} MB vs sketch "
        f"{mem['sketch_bytes'] / 1e6:.1f} MB ({mem['ratio']:.0f}x) over "
        f"{mem['vocab_rows']} vocab rows")
    out("observe us/batch: " + json.dumps(
        {k: round(v) for k, v in thr["observe_us_per_batch"].items()}))
    out(f"wrote {json_path}")
    return result


def bench_obs(out=print, json_path="BENCH_obs.json", steps=30, batch=512,
              reps=5):
    """Telemetry overhead on the reduced DLRM train step (DESIGN.md §10).

    Three variants of the SAME jitted step loop: telemetry off, telemetry
    on (metrics returned but never read — the async-dispatch steady
    state), and telemetry on with the ``MetricsPump`` draining every
    record lag steps late.  The telemetry reductions fuse into the step's
    single program (the ``train_step_telemetry`` audit spec pins the
    launch count), so the claim is <= 2% step-time overhead; min-of-reps
    suppresses host noise."""
    from repro.configs import dlrm_criteo
    from repro.data import ClickstreamConfig, clickstream_batches
    from repro.models import dlrm
    from repro.obs import MetricsPump, TelemetryConfig
    from repro.optim import sgd
    from repro.train.loop import init_state, make_train_step, split_buffers

    cfg = dlrm_criteo.reduced(emb_method="cce", cap=512)
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    raw = next(clickstream_batches(
        ClickstreamConfig(vocab_sizes=cfg.vocab_sizes, seed=0), batch
    ))
    batch_tree = {k: jnp.asarray(v)[None] for k, v in raw.items()
                  if k != "step"}

    def build(telemetry):
        return jax.jit(make_train_step(
            loss_fn, opt, lambda s: jnp.float32(0.05), static,
            telemetry=telemetry,
        ))

    def run_loop(step_fn, pump=None):
        """min-of-reps wall time per step for a `steps`-long loop."""
        best = float("inf")
        for _ in range(reps):
            state = init_state(params, opt, dyn)
            # warm: compile outside the timed region
            state, m = step_fn(state, batch_tree)
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for s in range(steps):
                state, m = step_fn(state, batch_tree)
                if pump is not None:
                    pump.push(s, m)
            if pump is not None:
                pump.flush()
            jax.block_until_ready(state)
            best = min(best, (time.perf_counter() - t0) / steps * 1e6)
        return best

    t_off = run_loop(build(None))
    t_on = run_loop(build(TelemetryConfig()))
    t_pump = run_loop(build(TelemetryConfig()), pump=MetricsPump(lag=8))

    result = {
        "backend": jax.default_backend(),
        "steps": steps,
        "batch": batch,
        "reps": reps,
        "step_us": {"off": t_off, "on": t_on, "on_pump_drain": t_pump},
        "overhead_pct": {
            "on": (t_on - t_off) / t_off * 100,
            "on_pump_drain": (t_pump - t_off) / t_off * 100,
        },
    }
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out(f"obs: step us off={t_off:.0f} on={t_on:.0f} on+pump={t_pump:.0f}")
    out("overhead pct: " + json.dumps(
        {k: round(v, 2) for k, v in result["overhead_pct"].items()}))
    out(f"wrote {json_path}")
    return result


def bench_shard(out=print, json_path="BENCH_shard.json"):
    """Replicated vs model-sharded DLRM train step at full Criteo scale.

    Everything here is ahead-of-time: the step is built from
    ShapeDtypeStructs, lowered, and compiled — no array is ever
    allocated, so the full-vocabulary comparison runs on a laptop.  Per
    variant we report the structural numbers the sharding PR claims:
    pallas launches per step (unchanged by sharding), the per-kind
    collective counts + ICI/DCN bytes of the partitioned module
    (``hlo_cost.analyze``), per-device entry-parameter bytes
    (``hlo_cost.liveness``), and the exact per-device state footprint —
    supertable slab, optimizer moments, pointer/stat buffers — read off
    the step's own output shardings via ``Sharding.shard_shape``."""
    import dataclasses
    import math

    from repro.analysis import walker
    from repro.configs import dlrm_criteo
    from repro.launch import hlo_cost
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_dlrm_train_step
    from repro.optim import sgd

    n = jax.device_count()
    if n < 2:
        raise SystemExit(
            f"--shard needs several devices, JAX found {n} (on a CPU host: "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4)"
        )

    def subtree_bytes(shape_tree, shard_tree):
        shapes = jax.tree_util.tree_leaves(shape_tree)
        shards = jax.tree_util.tree_leaves(shard_tree)
        glob = sum(s.size * s.dtype.itemsize for s in shapes)
        per = sum(
            math.prod(sh.shard_shape(s.shape)) * s.dtype.itemsize
            for s, sh in zip(shapes, shards)
        )
        return {"global": glob, "per_device": per}

    variants = {}
    for name, model in (("replicated", 1), ("sharded", n)):
        cfg = dataclasses.replace(dlrm_criteo.CONFIG, emb_k_multiple=model)
        mesh = make_host_mesh(data=1, model=model)
        jitted, (state_shape, batch_struct), (state_sh, _) = (
            build_dlrm_train_step(
                cfg, mesh, batch_size=32, accum=1,
                optimizer=sgd(momentum=0.9),
            )
        )
        launches = walker.count_primitive(
            jax.make_jaxpr(jitted)(state_shape, batch_struct), "pallas_call"
        )
        text = jitted.lower(state_shape, batch_struct).compile().as_text()
        cost = hlo_cost.analyze(text)
        live = hlo_cost.liveness(text)
        variants[name] = {
            "model_shards": model,
            "pallas_launches": launches,
            "collectives": {k: int(v) for k, v in sorted(cost.coll.items())},
            "ici_bytes": cost.ici_bytes,
            "dcn_bytes": cost.dcn_bytes,
            "entry_param_bytes_per_device": live.param_bytes,
            "state_bytes": {
                "total": subtree_bytes(state_shape, state_sh),
                "emb_slab": subtree_bytes(
                    state_shape.params["emb"], state_sh.params["emb"]
                ),
                "opt_moments": subtree_bytes(state_shape.opt, state_sh.opt),
                "emb_buffers": subtree_bytes(state_shape.ebuf, state_sh.ebuf),
            },
        }
        out(f"{name}: launches={launches} "
            f"collectives={variants[name]['collectives']} "
            f"state/device={variants[name]['state_bytes']['total']['per_device'] / 1e6:.1f} MB")

    rep = variants["replicated"]["state_bytes"]["total"]["per_device"]
    shd = variants["sharded"]["state_bytes"]["total"]["per_device"]
    result = {
        "backend": jax.default_backend(),
        "n_devices": n,
        "config": "dlrm_criteo (full Criteo vocabularies, AOT — no arrays)",
        "variants": variants,
        "per_device_state_ratio": rep / shd if shd else None,
    }
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out(f"per-device state: replicated {rep / 1e6:.1f} MB -> "
        f"sharded {shd / 1e6:.1f} MB ({rep / shd:.2f}x)")
    out(f"wrote {json_path}")
    return result


def bench_serve(out=print, json_path="BENCH_serve.json",
                run_log_path="BENCH_serve_run.jsonl",
                vocab_sizes=(10_000_000, 100_000, 1_000),
                n_requests=256, max_batch=16, zipf_s=1.0, heavy=4096):
    """Serve-path latency under Zipf traffic (serve/dlrm.py, ROADMAP 2).

    Three traffic scenarios through identical engines (CPU wall times —
    structural claims, not TPU latencies):

    * ``head``: every id drawn from the SpaceSaving head the cache holds
      — fully-hit batches, ZERO launches (the millions-of-users case the
      cache exists for: the heavy head answered without the supertable).
    * ``zipf``: bounded-Zipf(s) ids over the full vocab — mixed batches,
      compacted cold sub-batch per launch, realistic hit rates.
    * ``uncached``: the same Zipf traffic with the cache disabled —
      every batch pays the fused launch.

    The gated claim: head (cache-hit) p50 strictly below the uncached
    fused-launch p50."""
    import numpy as np

    from repro.models.dlrm import DLRMConfig
    from repro.models import dlrm
    from repro.obs.runlog import LatencyHistogram, RunLog
    from repro.serve.dlrm import DLRMServeEngine, ServeRequest
    from repro.stream import StreamConfig

    cfg = DLRMConfig(
        vocab_sizes=vocab_sizes, n_dense=13, emb_dim=16,
        bottom_mlp=(64, 16), top_mlp=(64, 1),
        emb_method="cce", emb_param_cap=4096 * 16,
    )
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    tracker = dlrm.make_id_tracker(cfg, StreamConfig(
        width=1 << 12, heavy=heavy, window=64, async_fold=False,
    ))
    rng = np.random.default_rng(0)

    # bounded Zipf(s): inverse-CDF over the harmonic weights — np.random
    # .zipf needs s > 1 and is unbounded, neither fits a fixed id space
    cdfs = []
    for v in vocab_sizes:
        w = 1.0 / np.arange(1, v + 1, dtype=np.float64) ** zipf_s
        cdf = np.cumsum(w)
        cdfs.append(cdf / cdf[-1])

    def zipf_batch(n):
        return np.stack(
            [np.searchsorted(c, rng.random(n)).astype(np.int64) for c in cdfs],
            axis=1,
        )

    tracker.observe({"sparse": zipf_batch(8192)})  # warm the heads

    def drive(eng, sparse, label):
        eng.hist = LatencyHistogram()
        eng.hist_hit = LatencyHistogram()
        eng.hist_cold = LatencyHistogram()
        eng.counters.clear()
        dense = rng.normal(size=(len(sparse), cfg.n_dense)).astype(np.float32)
        for s in range(0, len(sparse), max_batch):
            for i in range(s, min(s + max_batch, len(sparse))):
                eng.submit(ServeRequest(uid=i, dense=dense[i], sparse=sparse[i]))
            eng.drain()
        stats = eng.flush_stats()
        res = {
            "p50_s": eng.hist.percentile(50),
            "p99_s": eng.hist.percentile(99),
            **{k: stats[k] for k in (
                "n_requests", "n_batches", "n_launches", "launches_per_batch",
                "hit_rate_requests", "hit_rate_ids",
            )},
        }
        out(f"serve[{label}]: p50 {res['p50_s'] * 1e3:.2f} ms  "
            f"p99 {res['p99_s'] * 1e3:.2f} ms  "
            f"hit {res['hit_rate_requests']:.0%} req / "
            f"{res['hit_rate_ids']:.0%} ids  "
            f"launches/batch {res['launches_per_batch']:.2f}")
        return res

    with RunLog(run_log_path, manifest={"config": "bench_serve"}) as rl:
        cached = DLRMServeEngine(
            params, buffers, cfg, tracker=tracker, max_batch=max_batch,
            latency_budget_s=0.0, run_log=rl,
        )
        uncached = DLRMServeEngine(
            params, buffers, cfg, cache=False, max_batch=max_batch,
            latency_budget_s=0.0, run_log=rl,
        )
        # head traffic: Zipf over each feature's CACHED ids, so every
        # batch is answerable without the supertable
        head_cols = []
        for f in range(cfg.n_sparse):
            ids = cached.cache.ids[f]
            w = 1.0 / np.arange(1, ids.size + 1, dtype=np.float64) ** zipf_s
            cdf = np.cumsum(w)
            ranks = np.searchsorted(cdf / cdf[-1], rng.random(n_requests))
            head_cols.append(ids[ranks])
        head_sparse = np.stack(head_cols, axis=1)
        zipf_sparse = zipf_batch(n_requests)

        # compile outside the timed scenarios (hit + cold programs)
        cached.predict(np.zeros((max_batch, cfg.n_dense), np.float32),
                       head_sparse[:max_batch])
        cached.predict(np.zeros((max_batch, cfg.n_dense), np.float32),
                       zipf_sparse[:max_batch])
        uncached.predict(np.zeros((max_batch, cfg.n_dense), np.float32),
                         zipf_sparse[:max_batch])

        result = {
            "backend": jax.default_backend(),
            "vocab_sizes": list(vocab_sizes),
            "zipf_s": zipf_s,
            "n_requests": n_requests,
            "max_batch": max_batch,
            "cache_slots": cached.cache.n_slots,
            "head": drive(cached, head_sparse, "head"),
            "zipf": drive(cached, zipf_sparse, "zipf"),
            "uncached": drive(uncached, zipf_sparse, "uncached"),
        }
    result["hit_p50_below_uncached_p50"] = bool(
        result["head"]["p50_s"] < result["uncached"]["p50_s"]
    )
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2)
    out(f"cache-hit p50 below uncached p50: "
        f"{result['hit_p50_below_uncached_p50']}")
    out(f"wrote {json_path} + {run_log_path}")
    return result


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--collection", action="store_true",
                    help="only the looped-vs-fused collection bench")
    ap.add_argument("--stream", action="store_true",
                    help="only the dense-vs-sketch tracker bench")
    ap.add_argument("--fuse", action="store_true",
                    help="only the looped/3-group/unified launch bench")
    ap.add_argument("--shard", action="store_true",
                    help="replicated-vs-sharded AOT comparison (multi-device)")
    ap.add_argument("--obs", action="store_true",
                    help="telemetry off/on/on+pump step-overhead bench")
    ap.add_argument("--serve", action="store_true",
                    help="serve-engine latency under Zipf traffic "
                         "(hot cache vs uncached)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.serve:
        bench_serve(json_path=args.json or "BENCH_serve.json")
    elif args.obs:
        bench_obs(json_path=args.json or "BENCH_obs.json")
    elif args.stream:
        bench_stream(json_path=args.json or "BENCH_stream.json")
    elif args.collection:
        bench_collection(json_path=args.json or "BENCH_collection.json")
    elif args.fuse:
        bench_fuse(json_path=args.json or "BENCH_fuse.json")
    elif args.shard:
        bench_shard(json_path=args.json or "BENCH_shard.json")
    else:
        main()
        bench_collection(json_path=args.json or "BENCH_collection.json")
        bench_stream(json_path="BENCH_stream.json")
        bench_fuse(json_path="BENCH_fuse.json")
