"""A serving cell: ``DLRMServeEngine`` under an open loop of queries.

Queries arrive at the mix's fixed rate (Poisson), each a set of samples
that the engine's micro-batcher aggregates; a sample is one
``ServeRequest``.  A query's latency runs from its due time to the result
of its last sample.  The loop is one thread: it submits what is due, lets
the engine serve a micro-batch when one is ready, and otherwise waits for
the next due time or the batcher's deadline.  The harness's own spans
(``gen-submit``, ``engine-step``, ``idle-wait``) mark its calls in a trace.

Before the window the tracker sees a warm-up stream of the same traffic
(drawn apart from the served samples), so the SpaceSaving heads name the
hot ids as in a deployment, and every program the window can run is
compiled: the hit and cold programs and the tracker's cell counter at
every micro-batch size.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from harness import program, traffic, weights as W
from harness.trace import WINDOW_SPAN, Profile, compiles

DRAIN_S = 60.0  # answers may come this long after the window closes


def _warm(engine, trk, sampler, n_dense, rng, max_batch, warm_samples):
    """Fill the tracker's heads, then compile what the window runs, with
    ids as the engine hands them on (int64)."""
    if trk is not None:
        _, sparse = traffic.samples(sampler, n_dense, rng, warm_samples)
        sparse = sparse.astype(np.int64)
        for i in range(0, warm_samples - max_batch + 1, max_batch):
            trk.observe({trk.key: sparse[i:i + max_batch]})
        for n in range(1, max_batch):  # the engine observes every batch size
            trk.observe({trk.key: sparse[:n]})
        trk.flush()
        engine.refresh_cache(reason="warm-up")
    dense, sparse = traffic.samples(sampler, n_dense, rng, max_batch)
    sparse = sparse.astype(np.int64)
    engine.predict(dense, sparse)  # the cold program
    cache = engine.cache
    if cache is not None and len(cache.ids) == sparse.shape[1]:
        hit = np.stack([cache.ids[f][rng.integers(0, cache.ids[f].size, max_batch)]
                        for f in range(sparse.shape[1])], axis=1)
        engine.predict(dense, hit)  # the hit program
    if trk is not None:
        trk.flush()


class Setup:
    """The engine with its weights, warmed up, and the served samples."""

    def __init__(self, cfg, mix, ref, seed: int, engine_hook=None):
        from repro.serve.dlrm import DLRMServeEngine

        self.cfg, self.mix, self.seed = cfg, mix, seed
        pcfg = program.dlrm_config(cfg)
        program.check_tables(pcfg, W.table_shapes(cfg))
        self.make = W.make_fn(cfg, ref)
        self.key = W.seed_key(seed)
        params, bufs = self.make[0](self.key), self.make[1](self.key)
        prog_params, prog_bufs = program.to_program(pcfg, params, bufs)
        del params, bufs
        self.tracker = program.tracker(pcfg, cfg)
        max_batch = int(cfg["serve_max_batch"])
        self.engine = DLRMServeEngine(
            prog_params, prog_bufs, pcfg, tracker=self.tracker, max_batch=max_batch,
            latency_budget_s=float(cfg["serve_latency_budget_s"]))
        if engine_hook is not None:
            engine_hook(self.engine)
        sampler = traffic.IdSampler(cfg["vocab_sizes"], mix["ids"], mix.get("zipf_a", 1.1))
        _warm(self.engine, self.tracker, sampler, cfg["n_dense"],
              traffic.rng_for(seed, 5), max_batch, int(mix["warmup_samples"]))
        self.dense, self.sparse = traffic.samples(
            sampler, cfg["n_dense"], traffic.rng_for(seed, 6), int(mix["pool_samples"]))

    def close(self):
        self.engine = self.tracker = None
        gc.collect()


def window(st: Setup, mix: dict, seconds: float, seed: int, trace: bool,
           drain_s: float = DRAIN_S) -> dict:
    """Offer the mix's queries for ``seconds`` and wait for every answer
    (at most ``drain_s`` past the close)."""
    from repro.serve.dlrm import ServeRequest

    engine = st.engine
    due, sizes = traffic.query_schedule(mix, seconds, seed)
    starts = traffic.rng_for(seed, 7).integers(0, len(st.dense), len(due))
    nq = len(due)
    first_uid = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    q_of = np.repeat(np.arange(nq), sizes)
    idx_of = (np.repeat(starts, sizes) + np.arange(len(q_of)) - np.repeat(first_uid, sizes)) \
        % len(st.dense)
    n_samples = len(q_of)
    logit = np.full(n_samples, np.nan)
    done_at = np.full(n_samples, np.nan)
    dispatched = np.full(n_samples, np.nan)
    remaining = sizes.copy()
    submit_at = np.full(nq, np.nan)
    backlog = np.zeros(nq)
    batch_s: list[float] = []
    c0 = dict(engine.counters)
    clock = engine.clock
    gc.collect()
    n_compiled = compiles()
    t_setup = time.perf_counter()

    ann = jax.profiler.TraceAnnotation
    with Profile(trace) as prof:
        with ann(WINDOW_SPAN):
            t0 = clock()
            due_abs = t0 + due
            qi = served = 0
            deadline = t0 + seconds + drain_s
            while served < n_samples:
                now = clock()
                if now > deadline:
                    break
                if qi < nq and due_abs[qi] <= now:
                    with ann("gen-submit"):
                        while qi < nq and due_abs[qi] <= now:
                            submit_at[qi] = now
                            backlog[qi] = len(engine.batcher)
                            t_due = float(due_abs[qi])
                            for u in range(first_uid[qi], first_uid[qi] + sizes[qi]):
                                r = idx_of[u]
                                engine.submit(ServeRequest(int(u), st.dense[r], st.sparse[r],
                                                           t_due))
                            qi += 1
                            now = clock()
                if engine.batcher.ready():
                    t_b = clock()
                    with ann("engine-step"):
                        results = engine.step()
                    batch_s.append(clock() - t_b)
                    for res in results:
                        u = res.uid
                        logit[u] = res.logit
                        done_at[u] = due_abs[q_of[u]] + res.latency_s
                        dispatched[u] = t_b
                        remaining[q_of[u]] -= 1
                    served += len(results)
                    continue
                nxt = due_abs[qi] if qi < nq else np.inf
                if len(engine.batcher):
                    nxt = min(nxt, engine.batcher._pending[0].t_arrival
                              + engine.batcher.latency_budget_s)
                wait = nxt - clock()
                if wait > 0:
                    with ann("idle-wait"):
                        time.sleep(min(wait, 0.01))
            t_end = clock()
    window_compiles = compiles() - n_compiled
    peak = jax.devices()[0].memory_stats() or {}
    finished = remaining == 0
    q_done = np.full(nq, -np.inf)
    np.maximum.at(q_done, q_of, np.where(np.isfinite(done_at), done_at, -np.inf))
    return {
        "t_setup": t_setup,
        "window_s": t_end - t0,
        "attempted": nq,
        "failed": int(nq - finished.sum()),
        "finished": finished,
        "latency_s": (q_done - due_abs)[finished],
        "backlog": backlog,
        "peak_bytes": peak.get("peak_bytes_in_use"),
        "window_compiles": window_compiles,
        "trace": prof.trace,
        "samples": int(np.isfinite(done_at).sum()),
        "counters": {k: engine.counters[k] - c0.get(k, 0) for k in engine.counters},
        "batch_s": np.asarray(batch_s),
        "queue_s": (dispatched - due_abs[q_of])[np.isfinite(dispatched)],
        "lateness_s": (submit_at - due_abs)[np.isfinite(submit_at)],
        "sizes": sizes, "first_uid": first_uid, "idx_of": idx_of, "logit": logit,
    }


def check(st: Setup, ref, out: dict, precision: str, control: str | None) -> dict:
    """The served logits of a seeded sample of finished queries, the longest
    among them, against the reference's."""
    sizes, first_uid, finished = out["sizes"], out["first_uid"], out["finished"]
    done_q = np.flatnonzero(finished)
    if not len(done_q):
        return {"logit_gap": float("inf")}
    rng = traffic.rng_for(st.seed, 8)
    n_check = min(len(done_q), int(st.mix["check_queries"]))
    pick = set(rng.choice(done_q, n_check, replace=False).tolist())
    pick.add(int(done_q[np.argmax(sizes[done_q])]))
    uids = np.concatenate([first_uid[q] + np.arange(sizes[q]) for q in sorted(pick)])
    rows = out["idx_of"][uids]
    dense, sparse = st.dense[rows], st.sparse[rows]
    want = reference_logits(ref, st.make, st.key, dense, sparse, precision)
    got = out["logit"][uids]
    if control is not None:  # the control takes the program's place
        got = reference_logits(ref, st.make, st.key, dense, sparse, control)
    return {"logit_gap": float(np.max(np.abs(got - want)))}


def run(cell, cfg, mix, ref, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: str | None = None, engine_hook=None, drain_s: float = DRAIN_S):
    st = Setup(cfg, mix, ref, seed, engine_hook)
    out = window(st, mix, seconds, seed, trace, drain_s)
    out["setup_s"] = out["t_setup"] - t_start
    st.close()
    t_ref = time.perf_counter()
    out["numbers"] = check(st, ref, out, cfg["matmul_precision"], control)
    out["reference_s"] = time.perf_counter() - t_ref
    return out


def reference_logits(ref, make, key, dense, sparse, precision: str, block: int = 4096):
    params, bufs = make[0](key), make[1](key)
    fn = jax.jit(lambda p, b, d, s: ref.logits(p, b, d, s, precision))
    out = []
    n = len(dense)
    pad = -n % block  # one block shape, one compile
    dense = np.concatenate([dense, np.zeros((pad, dense.shape[1]), dense.dtype)])
    sparse = np.concatenate([sparse, np.zeros((pad, sparse.shape[1]), sparse.dtype)])
    for i in range(0, n, block):
        out.append(np.asarray(fn(params, bufs, dense[i:i + block], sparse[i:i + block])))
    return np.concatenate(out)[:n] if out else np.zeros(0)
