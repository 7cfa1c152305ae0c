"""A tiny size of every cell for the benchmark's own tests on the CPU: the
same code paths as a chip run, at shapes the CPU's interpreter holds."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import bench  # noqa: E402

CONFIG = {
    "vocab_sizes": [1000, 5000, 20000, 100, 50000, 3],
    "bottom_mlp": [64, 32, 16], "top_mlp": [64, 1],
    "emb_param_cap": 512,
    "stream": {"width": 2048, "depth": 4, "heavy": 128, "ring": 2048,
               "decay": 0.9, "window": 8, "async_fold": True},
    "train_batch": 64, "serve_max_batch": 16, "head_check_top": 8,
}
TRAFFIC = {
    "train": {"pool_batches": 6},
    "serve": {"rate_qps": 40.0, "size_min": 5, "size_max": 20, "warmup_samples": 256,
              "pool_samples": 512, "check_queries": 8},
}


#: cells whose files are here but which ``BENCHMARK.json`` does not list yet
UNLISTED = {
    "serve_kaggle_cce_zipf": {"name": "serve_kaggle_cce_zipf", "config": "dlrm_criteo_kaggle",
                              "traffic": "zipf_queries", "chips": 1},
    "serve_kaggle_cce_uniform": {"name": "serve_kaggle_cce_uniform",
                                 "config": "dlrm_criteo_kaggle",
                                 "traffic": "uniform_queries", "chips": 1},
}


def overrides(mode: str, config: dict | None = None, workload: str | None = None) -> dict:
    cfg = dict(CONFIG)
    if config:
        cfg.update(config)
    out = {"config": cfg, "traffic": TRAFFIC[mode]}
    if workload in UNLISTED:
        out["cell"] = UNLISTED[workload]
    return out


def run(workload: str, mode: str, *, seed: int = 7, seconds: float = 0.5,
        trace: bool = False, config: dict | None = None, **kw) -> dict:
    """One tiny run of ``workload`` on the CPU, without the look for a chip."""
    return bench.run(workload, seed, seconds, trace,
                     overrides=overrides(mode, config, workload), require_chip=False, **kw)
