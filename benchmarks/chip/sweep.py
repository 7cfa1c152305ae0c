#!/usr/bin/env python3
"""Find a serving cell's knee on the chip: the highest query rate the engine
sustains without a growing backlog.  One process, one set-up, one window
per rate (the rates in increasing order):

    python3 benchmarks/chip/sweep.py --workload serve_kaggle_cce_zipf \
        --seed 1 --seconds 15 --rates 20 40 60 80

Prints one JSON line per rate: the query latency p50/p99, the mean latency
of the first and last quarter of the queries (a backlog that grows shows
as a last quarter far above the first), the generator's lateness p99, the
pending samples at submission (mean, last quarter), and the samples/s
served.
"""
from __future__ import annotations

import argparse
import json
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench._environment()
    spec = bench.load_spec(args.workload)
    bench.check_device(int(spec["cell"]["chips"]))
    import jax
    import numpy as np

    from harness import serve

    cfg, mix = spec["cfg"], spec["mix"]
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    ref = bench.load_module(spec["reference"])
    st = serve.Setup(cfg, mix, ref, args.seed)
    for i, rate in enumerate(sorted(args.rates)):
        m = dict(mix, rate_qps=rate)
        out = serve.window(st, m, args.seconds, args.seed + i, False)
        lat = np.asarray(out["latency_s"]) * 1e3
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "workload": args.workload, "rate_qps": rate, "queries": out["attempted"],
            "failed": out["failed"],
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
            "first_quarter_mean_ms": float(lat[:q].mean()) if len(lat) else None,
            "last_quarter_mean_ms": float(lat[-q:].mean()) if len(lat) else None,
            "lateness_p99_ms": float(np.percentile(out["lateness_s"], 99) * 1e3),
            "backlog_mean": float(out["backlog"].mean()),
            "backlog_last_quarter": float(out["backlog"][-q:].mean()),
            "samples_per_s": out["samples"] / out["window_s"],
            "batch_ms_mean": float(out["batch_s"].mean() * 1e3),
            "batches": int(len(out["batch_s"])),
            "hit_share": out["counters"]["n_id_hits"] / max(1, out["counters"]["n_id_lookups"]),
        }), flush=True)
        if out["failed"]:
            break
    st.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
