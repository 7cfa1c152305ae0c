#!/usr/bin/env python3
"""The chip benchmark: runs one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<name>.json``, with the plain
reference it names beside it) and a traffic mix (``traffic/<name>.json``,
whose ``mode`` picks the runner in ``harness/``).  Each metric is read by
its own reader, ``metrics/<name>.py``; ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` traces the window and prints its
per-layer metrics.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number that
decided ``correct`` beside its limit (also the last lines of standard
error).  The run needs a TPU with as many chips as the cell asks for;
without one it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def _environment() -> None:
    """Before anything compiles: the compile cache at a fixed path inside
    the checkout (whatever the machine sets), every program cached however
    quickly it compiled and none evicted, and the program and the harness
    on the path."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    for p in (str(HERE), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_spec(workload: str, overrides: dict | None = None) -> dict:
    """The cell, its configuration, its traffic and its metrics, found by
    the names in ``BENCHMARK.json``."""
    from harness import traffic

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    overrides = overrides or {}
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells and "cell" not in overrides:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells.get(workload) or overrides["cell"]
    config_dir = Path(overrides.get("config_dir", HERE / "configs"))
    with open(config_dir / f"{cell['config']}.json") as f:
        cfg = json.load(f)
    mix = traffic.load_mix(cell["traffic"])
    cfg.update(overrides.get("config", {}))
    mix.update(overrides.get("traffic", {}))

    def reported(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)]
    return {"cell": cell, "cfg": cfg, "mix": mix, "end_to_end": e2e, "per_layer": layer,
            "reference": config_dir / f"{cfg['reference']}.py"}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_device(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        overrides: dict | None = None, require_chip: bool = True,
        control: str | None = None, hooks: dict | None = None,
        t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object.  ``require_chip``,
    ``overrides``, ``control`` and ``hooks`` exist for the benchmark's own
    tests and calibration only."""
    _environment()
    import repro  # noqa: F401  (the program: a checkout without it cannot run)

    spec = load_spec(workload, overrides)
    if require_chip:
        check_device(int(spec["cell"]["chips"]))
    import jax

    from harness import work

    cfg, mix = spec["cfg"], spec["mix"]
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    dev = device_info()
    ref = load_module(spec["reference"])
    runner = load_module(HERE / "harness" / f"{mix['mode']}.py")
    out = runner.run(spec["cell"], cfg, mix, ref, seed, seconds, trace,
                     T_START if t_start is None else t_start,
                     control=control, **(hooks or {}))

    from harness import trace as tr

    red = tr.reduce(out["trace"], tr.SPAN_NAMES) if out.get("trace") is not None else None
    ctx = {"mode": mix["mode"], "cfg": cfg, "mix": mix, "run": out, "reduction": red,
           "peak": work.peaks(dev["kind"]) if require_chip else None,
           "trace": out.get("trace")}
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    from harness import compare

    ok, checks = compare.judge(out["numbers"], cfg["limits"][mix["mode"]])
    dev["memory_peak_bytes"] = out["peak_bytes"]
    if red is not None:
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
    result = {"correct": bool(ok and out["failed"] == 0), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if red is not None:
        result["breakdown"] = red.breakdown()
    result["checks"] = checks
    result["window_compiles"] = out.get("window_compiles")
    result["reference_s"] = out.get("reference_s")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a non-negative whole number")
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(f"programs compiled or loaded inside the window: {result.pop('window_compiles')}",
          file=sys.stderr)
    print(f"reference and comparison: {result.pop('reference_s')} s", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
