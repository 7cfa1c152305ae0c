#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` on the chip, at a cell's own
size, over many seeds: the program's (sound runs set the lower reading),
the control's (the reference in the program's place at the precision
below the configuration's) and, for training, the fault of half the batch
left out (the reference in the program's place); these set the upper
readings.  A state left unchanged reads 1 by construction and needs no run.

    python3 benchmarks/chip/calibrate.py --workload train_kaggle_cce \
        --control high --seeds 1 2 3 ... [--faults cells_to_zero ... \
        --fault-seeds 1 2 3]

Training runs the set-up's checked steps and one chunk of window per seed,
and names the leaves behind the worst gradient and change gaps; each fault
of ``harness/faults.py`` named by ``--faults`` is planted in the program
and run with a window of ``--seconds`` on each of ``--fault-seeds``.
Serving builds the engine once and loads each further seed's weights
through ``DLRMServeEngine.update_state``, then serves a short window at
the cell's rate.  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bench._environment()
    spec = bench.load_spec(args.workload)
    bench.check_device(int(spec["cell"]["chips"]))
    import jax
    import numpy as np

    from harness import compare, program, serve, train
    from harness import weights as W

    cfg, mix = spec["cfg"], spec["mix"]
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    ref = bench.load_module(spec["reference"])
    if mix["mode"] == "train":
        from harness import faults

        names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(W.make_fn(cfg, ref)[0], W.seed_key(0)))[0]]

        def half(batch):
            return {k: v[: len(v) // 2] for k, v in batch.items()}

        def worst(prog, ref, k=3):
            """The leaves behind the widest gaps: (name, gap, ref norm)."""
            p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
            gaps = np.abs(p - r) / np.maximum(r, np.median(r))
            return [[names[i], float(gaps[i]), float(r[i])] for i in np.argsort(-gaps)[:k]]

        for seed in args.seeds:
            t0 = time.perf_counter()
            out = train.run(spec["cell"], cfg, mix, ref, seed, 0.01, False, t0)
            make, key, pool = out["inputs"]
            r, p = out["readings"]["reference"], out["readings"]["program"]
            ctl = compare.train_numbers(
                train.reference_readings(ref, make, key, pool, cfg, args.control), r)
            flt = compare.train_numbers(
                train.reference_readings(ref, make, key, pool, cfg, cfg["matmul_precision"],
                                         batch_fn=half), r)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": out["numbers"], "control": ctl,
                              "half_batch": flt, "steps": out["steps"],
                              "worst_grad": worst(p["grad"], r["grad"]),
                              "worst_change": worst(p["change"], r["change"]),
                              "s": time.perf_counter() - t0}), flush=True)
        for name in args.faults:
            for seed in args.fault_seeds:
                t0 = time.perf_counter()
                out = train.run(spec["cell"], cfg, mix, ref, seed, args.seconds, False, t0,
                                **faults.hooks(name))
                print(json.dumps({"workload": args.workload, "seed": seed, "fault": name,
                                  "program": out["numbers"], "steps": out["steps"],
                                  "s": time.perf_counter() - t0}), flush=True)
        return 0

    st = None
    for seed in args.seeds:
        t0 = time.perf_counter()
        if st is None:
            st = serve.Setup(cfg, mix, ref, seed)
        else:  # the next seed's weights and samples in the same engine
            from harness import traffic

            st.seed, st.key = seed, W.seed_key(seed)
            params, bufs = st.make[0](st.key), st.make[1](st.key)
            p, b = program.to_program(st.engine.cfg, params, bufs)
            del params, bufs
            st.engine.update_state(p, b)
            sampler = traffic.IdSampler(cfg["vocab_sizes"], mix["ids"], mix.get("zipf_a", 1.1))
            st.dense, st.sparse = traffic.samples(
                sampler, cfg["n_dense"], traffic.rng_for(seed, 6), int(mix["pool_samples"]))
        out = serve.window(st, mix, args.seconds, seed, False)
        prog = serve.check(st, ref, out, cfg["matmul_precision"], None)
        ctl = serve.check(st, ref, out, cfg["matmul_precision"], args.control)
        print(json.dumps({"workload": args.workload, "seed": seed, "failed": out["failed"],
                          "program": prog, "control": ctl,
                          "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
