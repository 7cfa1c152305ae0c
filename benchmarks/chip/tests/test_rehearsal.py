"""Every cell end to end on the CPU at a tiny size, and the command's
refusals: no chip, no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny

CELLS = [("train_kaggle_cce", "train"), ("train_kaggle_full", "train"),
         ("serve_kaggle_cce_zipf", "serve"), ("serve_kaggle_cce_uniform", "serve")]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload,mode", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_and_is_correct(workload, mode, traced):
    r = tiny.run(workload, mode, seed=2**31 + 12345, seconds=0.5, trace=traced)
    assert r.pop("window_compiles") == 0 and r.pop("reference_s") > 0
    assert list(r)[:5] == KEYS and list(r)[-1] == "checks"
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert "kind" in r["device"]
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    if not traced:
        # the CPU has no memory peak; a cell not listed yet reports set-up only
        want = {"setup_s"} | ({"train_examples_per_s"} if mode == "train" else set())
        assert set(r["metrics"]) == want
    # a CPU run never writes a device metric
    assert not any(k.startswith(("device_idle", "step_device", "cce_lookup", "train_mfu",
                                 "serve_mfu")) for k in r["metrics"])


def test_same_seed_same_inputs():
    from harness import traffic

    mix = {**traffic.load_mix("zipf_train"), "pool_batches": 2}
    a = traffic.train_pool(mix, [100, 5000], 13, 16, 2**33 + 1)
    b = traffic.train_pool(mix, [100, 5000], 13, 16, 2**33 + 1)
    c = traffic.train_pool(mix, [100, 5000], 13, 16, 2**33 + 2)
    assert all((x[k] == y[k]).all() for x, y in zip(a, b) for k in x)
    assert not (a[0]["sparse"] == c[0]["sparse"]).all()
    q = {**traffic.load_mix("zipf_queries"), "rate_qps": 50.0}
    d1, s1 = traffic.query_schedule(q, 4.0, 1)
    d2, s2 = traffic.query_schedule(q, 4.0, 2)
    assert len(d1) == 200 and sorted(s1) == sorted(s2) and not (s1 == s2).all()
    assert d1[0] == 0 and d1[-1] < 4.0


def _cli(cwd, env):
    cmd = [sys.executable, "benchmarks/chip/bench.py", "--workload", "train_kaggle_cce",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_no_result():
    root = tiny.bench.ROOT
    p = _cli(root, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_without_the_program_no_result(tmp_path):
    root = tiny.bench.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for path in json.loads((root / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(root / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
