"""Sharded lowering on a small forced-device-count mesh — the in-repo
guard for the full dry-run (which needs 512 devices and its own process).

Runs in a subprocess so the XLA device-count flag never leaks into the
test session (conftest asserts that).
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro import compat, configs
from repro.launch import steps, hlo_cost
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
out = {}
with compat.set_mesh(mesh):
    cfg = configs.get("qwen2-1.5b", n_layers=2, d_model=512, n_heads=4,
                      n_kv_heads=2, head_dim=128, d_ff=1024, vocab=4096,
                      emb_budget=4096*512//8, train_microbatch=2)
    jitted, (state_shape, batch_sds), _ = steps.build_train_step(cfg, mesh, "train_4k")
    compiled = jitted.lower(state_shape, batch_sds).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    out["train"] = {"flops": cost.flops, "coll": cost.coll,
                    "ici": cost.ici_bytes}
    jitted, args = steps.build_serve_step(cfg, mesh, "decode_32k")
    compiled = jitted.lower(*args).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    out["decode"] = {"flops": cost.flops, "coll": cost.coll}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_lowering_end_to_end():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["train"]["flops"] > 1e9
    assert "all-reduce" in out["train"]["coll"] or "reduce-scatter" in out["train"]["coll"]
    assert out["decode"]["flops"] > 0
