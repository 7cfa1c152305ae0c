"""A training cell: the entry point's donated one-device step inside the
program's ``Trainer``, fed from a pool of the mix's batches.

Set-up builds the trainer once, drives it from the seed through its first
three steps (the readings that decide ``correct``) and a few more, so that
every program the window runs has run, and hands the same object to the
window.  The window calls the trainer in chunks of steps until ``--seconds``
have passed, and its clock stops once the final state is ready and the
tracker has folded every batch.  The reference's three steps run after the
window, once the program's state is freed; the tracker's heads are read
after the three steps and after the window, against the exact counts of
the ids it was fed.
"""
from __future__ import annotations

import gc
import itertools
import time

import jax
import numpy as np

from harness import compare, program, traffic, weights as W
from harness.trace import WINDOW_SPAN, Profile, compiles

N_CHECKED = 3  # steps the reference follows
N_WARM = 12  # further set-up steps: the window's programs run and the fold thread is busy
CHUNK = 32  # steps a call into the trainer runs inside the window


def _capture_deltas(trk, out: list):
    """Record the in-step sketch deltas the trainer hands the tracker."""
    observe = trk.observe

    def wrapped(batch, *, delta=None):
        out.append(delta)
        return observe(batch, delta=delta)

    trk.observe = wrapped
    return lambda: delattr(trk, "observe")


def _heads(trk) -> dict:
    """{feature: (ids, counts)} of the tracker's SpaceSaving heads, once
    every observed batch is folded."""
    trk.flush()
    return {f: trk.features[f].hh.head() for f in trk.tracked}


def tracker_numbers(ref, cfg, pool, tracked, deltas, heads) -> dict:
    """The in-step sketch deltas of the first steps and the heads read
    after ``n_fed`` batches ({n_fed: heads}), against the reference: the
    share missing from every reading, the count gap from the last alone
    (after three steps a count of a few ids is off by a whole id whenever
    the sketch's estimate that admitted it collided in every row)."""
    top = int(cfg["head_check_top"])
    want = [ref.sketch_delta(pool[i]["sparse"], tracked, cfg) for i in range(len(deltas))]
    out = {"sketch_delta_gap": compare.sketch_delta_gap(deltas, want)}
    read = {n: compare.head_numbers(h, ref.fed_counts(pool, n, tracked, cfg), top)
            for n, h in heads.items()}
    out["head_miss_share"] = max(r["head_miss_share"] for r in read.values())
    out["head_count_gap"] = read[max(read)]["head_count_gap"]
    return out


def _readings(p0, after1, after3, losses, lr):
    norms = jax.jit(compare.diff_norms)
    return {
        "losses": [float(x) for x in losses],
        "grad": np.asarray(norms(p0, after1)) / lr,
        "change": np.asarray(norms(p0, after3)),
    }


def reference_readings(ref, make, key, pool, cfg, precision: str, *, batch_fn=None):
    """The plain reference's first three steps from the same weights and
    batches, at ``precision``, through its optimizer with the state that
    ``ref.init_opt`` gives; read as the program's are read."""
    p0, bufs = make[0](key), make[1](key)
    step = jax.jit(lambda p, s, b, x: ref.train_step(p, s, b, x, cfg, precision))
    params, state, losses, after1 = p0, ref.init_opt(p0, cfg), [], None
    for i in range(N_CHECKED):
        batch = pool[i] if batch_fn is None else batch_fn(pool[i])
        params, state, loss = step(params, state, bufs, batch)
        losses.append(loss)
        if i == 0:
            after1 = params
    return _readings(p0, after1, params, losses, cfg["optimizer"]["lr"])


def run(cell, cfg, mix, ref, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: str | None = None, step_hook=None, tracker_hook=None):
    from repro.train.loop import Trainer, init_state, split_buffers

    batch = int(cfg["train_batch"])
    pcfg = program.dlrm_config(cfg)
    shapes = W.table_shapes(cfg)
    program.check_tables(pcfg, shapes)
    make = W.make_fn(cfg, ref)
    key = W.seed_key(seed)
    params, bufs = make[0](key), make[1](key)
    prog_params, prog_bufs = program.to_program(pcfg, params, bufs)
    del params, bufs
    dyn, static = split_buffers(prog_bufs)
    trk = program.tracker(pcfg, cfg)
    if tracker_hook is not None and trk is not None:  # the benchmark's fault tests
        tracker_hook(trk)
    step, optimizer = program.train_step(pcfg, cfg, static, trk)
    if step_hook is not None:  # the benchmark's fault tests break the step here
        step = step_hook(step)
    state = init_state(prog_params, optimizer, dyn)
    del prog_params, prog_bufs, dyn
    pool = traffic.train_pool(mix, cfg["vocab_sizes"], cfg["n_dense"], batch, seed,
                              bag_sizes=cfg.get("bag_sizes"))
    trainer = Trainer(step, state, static, itertools.cycle(pool), id_tracker=trk,
                      seed=seed % (1 << 31))
    del state

    # the first three steps, read as they leave the window's own call
    lr = cfg["optimizer"]["lr"]
    deltas: list = []
    restore = _capture_deltas(trk, deltas) if trk is not None else (lambda: None)
    # p0 comes again from the same program (same bits) and is never alive
    # during a step; the program's layout is unstacked inside the norms
    norms = jax.jit(lambda p0, p: compare.diff_norms(p0, program.per_feature(pcfg, p)))
    trainer.run(1)
    g1 = np.asarray(norms(make[0](key), trainer.state.params)) / lr
    trainer.run(N_CHECKED - 1)
    restore()
    change = np.asarray(norms(make[0](key), trainer.state.params))
    prog_reading = {"losses": [h["loss"] for h in list(trainer.history)[:N_CHECKED]],
                    "grad": g1, "change": change}
    deltas = [np.asarray(d) for d in deltas if d is not None]
    heads = {N_CHECKED: _heads(trk)} if trk is not None else {}

    trainer.run(N_WARM)
    jax.block_until_ready(trainer.state)
    if trk is not None:
        trk.flush()
    n_fed = N_CHECKED + N_WARM
    setup_s = time.perf_counter() - t_start

    n_compiled = compiles()
    n_window = 0
    with Profile(trace) as prof:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            while n_window == 0 or time.perf_counter() - t0 < seconds:
                trainer.run(CHUNK)
                n_window += CHUNK
            jax.block_until_ready(trainer.state)
            if trk is not None:
                trk.flush()
            window_s = time.perf_counter() - t0
    window_compiles = compiles() - n_compiled
    n_fed += n_window
    if trk is not None:
        heads[n_fed] = _heads(trk)
    losses = [h["loss"] for h in list(trainer.history)[-n_window:]]
    peak = jax.devices()[0].memory_stats() or {}
    peak_bytes = peak.get("peak_bytes_in_use")

    trainer = step = trk = None
    gc.collect()

    t_ref = time.perf_counter()
    ref_reading = reference_readings(ref, make, key, pool, cfg, cfg["matmul_precision"])
    if control is not None:  # the control takes the program's place
        prog_reading = reference_readings(ref, make, key, pool, cfg, control)
    numbers = compare.train_numbers(prog_reading, ref_reading)
    if heads:
        tracked = [f for f, s in enumerate(W.table_shapes(cfg)) if s.kind == "cce"]
        numbers.update(tracker_numbers(ref, cfg, pool, tracked, deltas, heads))
    reference_s = time.perf_counter() - t_ref

    n_fail = int(np.sum(~np.isfinite(np.asarray(losses, np.float64))))
    return {
        "setup_s": setup_s,
        "window_s": window_s,
        "steps": n_window,
        "examples": n_window * batch,
        "attempted": n_window,
        "failed": n_fail,
        "peak_bytes": peak_bytes,
        "window_compiles": window_compiles,
        "numbers": numbers,
        "reference_s": reference_s,
        "trace": prof.trace,
        "readings": {"program": prog_reading, "reference": ref_reading},
        "inputs": (make, key, pool),
    }
