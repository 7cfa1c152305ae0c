"""Cost-budget suite: the peak-live estimator, the quantitative rules,
budget-file roundtrips/tolerances, and the CLI regression gate.

Same philosophy as test_analysis.py: the budgets are a CI gate, so every
rule gets a planted regression it MUST flag and a clean case it MUST
pass.  Handcrafted HLO modules pin the liveness estimator's contract
(DESIGN.md §8) line by line; the planted fp64 upcast doubles real HBM
bytes through the real AOT-compile path; the CLI test doctors a budget
file and demands a non-zero exit.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.budget import (
    DEFAULT_TOLERANCES,
    BudgetFile,
    allowed_max,
    diff_profiles,
)
from repro.analysis.cost_rules import (
    BytesBudget,
    CollectiveBudget,
    CostProfile,
    FlopBudget,
    NoReplicatedParam,
    PeakMemoryBudget,
    cost_profile,
)
from repro.analysis.program import AuditProgram
from repro.launch import hlo_cost

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --- peak-live-buffer estimator on handcrafted HLO --------------------------

_STRAIGHT_LINE = """\
HloModule toy

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %a = f32[256]{0} add(f32[256]{0} %p0, f32[256]{0} %p0)
  %b = f32[256]{0} multiply(f32[256]{0} %a, f32[256]{0} %a)
  ROOT %c = f32[256]{0} add(f32[256]{0} %b, f32[256]{0} %b)
}
"""

# same dataflow with a tuple/get-tuple-element detour: aliases must add
# no storage, so the peak is identical to the straight-line module
_ALIASED = """\
HloModule toy

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %a = f32[256]{0} add(f32[256]{0} %p0, f32[256]{0} %p0)
  %b = f32[256]{0} multiply(f32[256]{0} %a, f32[256]{0} %a)
  %t = (f32[256]{0}) tuple(f32[256]{0} %b)
  %g = f32[256]{0} get-tuple-element((f32[256]{0}) %t), index=0
  ROOT %c = f32[256]{0} add(f32[256]{0} %g, f32[256]{0} %g)
}
"""

_WHILE = """\
HloModule loop

%cond (x: (s32[], f32[1024])) -> pred[] {
  %x = (s32[], f32[1024]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[1024]) %x), index=0
  %k = s32[] constant(10)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %k), direction=LT
}

%body (y: (s32[], f32[1024])) -> (s32[], f32[1024]) {
  %y = (s32[], f32[1024]) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[1024]) %y), index=0
  %v = f32[1024]{0} get-tuple-element((s32[], f32[1024]) %y), index=1
  %one = s32[] constant(1)
  %j2 = s32[] add(s32[] %j, s32[] %one)
  %tmp = f32[1024]{0} add(f32[1024]{0} %v, f32[1024]{0} %v)
  %tmp2 = f32[1024]{0} multiply(f32[1024]{0} %tmp, f32[1024]{0} %tmp)
  ROOT %r = (s32[], f32[1024]) tuple(s32[] %j2, f32[1024]{0} %tmp2)
}

ENTRY %main (p0: (s32[], f32[1024])) -> (s32[], f32[1024]) {
  %p0 = (s32[], f32[1024]) parameter(0)
  ROOT %w = (s32[], f32[1024]) while((s32[], f32[1024]) %p0), condition=%cond, body=%body
}
"""


def test_liveness_straight_line_counts_two_live_buffers():
    est = hlo_cost.liveness(_STRAIGHT_LINE)
    # at every step exactly two 1 KiB buffers overlap (producer+consumer)
    assert est.peak_bytes == 2 * 256 * 4
    assert est.param_bytes == 256 * 4


def test_liveness_tuple_gte_alias_adds_no_storage():
    assert (
        hlo_cost.liveness(_ALIASED).peak_bytes
        == hlo_cost.liveness(_STRAIGHT_LINE).peak_bytes
    )


def test_liveness_while_adds_body_peak_minus_params():
    est = hlo_cost.liveness(_WHILE)
    carry = 4 + 1024 * 4  # (s32[], f32[1024])
    # body peak: carry (live until its last gte-aliased use at %tmp)
    # + %j2 + %tmp all overlap; minus the carry param, which aliases the
    # caller's buffer, the body contributes j2 + tmp on top of the entry
    body_extra = 4 + 1024 * 4
    # entry: carry param + while result live together at the call site
    assert est.peak_bytes == 2 * carry + body_extra
    assert est.param_bytes == carry


def test_liveness_runs_on_a_real_compiled_module():
    def f(x):
        return jnp.tanh(x @ x.T).sum()

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ).compile().as_text()
    est = hlo_cost.liveness(text)
    # at least the input buffer must be live, and params are counted
    assert est.peak_bytes >= 64 * 64 * 4
    assert est.param_bytes == 64 * 64 * 4


# --- CostProfile via the abstract AOT-compile path --------------------------


def _profile_of(fn, *args, **kw):
    return cost_profile(AuditProgram.capture(fn, *args, name="toy", **kw))


def test_planted_fp64_upcast_blows_the_bytes_and_peak_budgets():
    n = 1 << 16
    f32 = _profile_of(lambda x: x * 2.0, jax.ShapeDtypeStruct((n,), jnp.float32))
    with jax.enable_x64(True):
        f64 = _profile_of(
            lambda x: x * 2.0, jax.ShapeDtypeStruct((n,), jnp.float64)
        )
    # the planted regression: fp64 doubles every byte metric
    assert f64.hbm_bytes == 2 * f32.hbm_bytes
    assert f64.peak_bytes == 2 * f32.peak_bytes
    with jax.enable_x64(True):
        prog = AuditProgram.capture(
            lambda x: x * 2.0, jax.ShapeDtypeStruct((n,), jnp.float64),
            name="toy",
        )
        found = BytesBudget(max_bytes=f32.hbm_bytes, baseline=f32.hbm_bytes).check(prog)
        assert len(found) == 1 and found[0].rule == "bytes-budget"
        assert "committed baseline" in found[0].message
        found = PeakMemoryBudget(max_bytes=f32.peak_bytes).check(prog)
        assert len(found) == 1 and found[0].rule == "peak-memory-budget"


def test_flop_budget_flags_doubled_matmul_work():
    m = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    one = _profile_of(lambda a, b: a @ b, m, m)
    assert one.flops == 2 * 64 * 64 * 64
    prog = AuditProgram.capture(lambda a, b: (a @ b) @ b, m, m, name="toy")
    found = FlopBudget(max_flops=one.flops).check(prog)
    assert len(found) == 1 and found[0].rule == "flop-budget"
    assert FlopBudget(max_flops=2 * one.flops).check(prog) == []


def _stub_program(profile: CostProfile) -> AuditProgram:
    prog = AuditProgram(name="stub", closed=None, invar_labels=())
    prog._cost_profile = profile
    return prog


def test_collective_budget_default_allows_nothing():
    clean = _stub_program(CostProfile())
    assert CollectiveBudget().check(clean) == []

    chatty = _stub_program(CostProfile(
        ici_bytes=1000.0, collectives={"all-reduce": 2.0}
    ))
    found = CollectiveBudget().check(chatty)
    assert {f.rule for f in found} == {"collective-budget"}
    assert len(found) == 2  # disallowed kind + ici bytes over the 0 cap
    assert CollectiveBudget(
        allow=("all-reduce",), max_ici_bytes=1000.0
    ).check(chatty) == []
    # bytes cap binds even when the kind is allowed
    found = CollectiveBudget(allow=("all-reduce",), max_ici_bytes=999.0).check(chatty)
    assert len(found) == 1 and "ici_bytes" in found[0].message


_REPLICATED_HLO = """\
HloModule jit_f, num_partitions=4

ENTRY %main (p0: f32[524288]) -> f32[524288] {
  %p0 = f32[524288]{0} parameter(0)
  ROOT %m = f32[524288]{0} multiply(f32[524288]{0} %p0, f32[524288]{0} %p0)
}
"""

_SHARDED_HLO = """\
HloModule jit_f, num_partitions=4

ENTRY %main (p0: f32[131072]) -> f32[131072] {
  %p0 = f32[131072]{0} parameter(0)
  ROOT %m = f32[131072]{0} multiply(f32[131072]{0} %p0, f32[131072]{0} %p0)
}
"""


def _captured_big_input():
    big = jax.ShapeDtypeStruct((1 << 19,), jnp.float32)  # 2 MiB
    return AuditProgram.capture(lambda d: d["w"] * 2.0, {"w": big}, name="toy")


def test_no_replicated_param_flags_full_size_leaf_under_partitions():
    prog = _captured_big_input()
    prog._compiled_text = _REPLICATED_HLO
    found = NoReplicatedParam().check(prog)
    assert len(found) == 1 and "'w'" in found[0].where
    assert "replicated on every device" in found[0].message
    # the allowlist names the leaf replicated by contract
    prog2 = _captured_big_input()
    prog2._compiled_text = _REPLICATED_HLO
    assert NoReplicatedParam(allow=("w",)).check(prog2) == []
    # instance-level severity downgrades documentation-only findings
    prog3 = _captured_big_input()
    prog3._compiled_text = _REPLICATED_HLO
    assert NoReplicatedParam(severity="warning").check(prog3)[0].severity == "warning"


def test_no_replicated_param_passes_on_sharded_leaf():
    prog = _captured_big_input()
    prog._compiled_text = _SHARDED_HLO
    assert NoReplicatedParam().check(prog) == []


def test_no_replicated_param_refuses_single_partition():
    prog = _captured_big_input()
    found = NoReplicatedParam().check(prog)  # real compile: 1 partition
    assert len(found) == 1 and "single partition" in found[0].message


# --- budget files: roundtrip, tolerances, diffs -----------------------------


def _profiles():
    return {
        "fwd": CostProfile(flops=1e9, hbm_bytes=2e9, peak_bytes=5e8),
        "step": CostProfile(
            flops=4e9, hbm_bytes=8e9, peak_bytes=1e9,
            ici_bytes=1e6, collectives={"all-reduce": 4.0}, num_partitions=4,
        ),
    }


def test_budget_file_roundtrip(tmp_path):
    bf = BudgetFile.from_profiles("toy", _profiles())
    path = str(tmp_path / "toy.json")
    bf.save(path)
    loaded = BudgetFile.load(path)
    assert loaded.to_dict() == bf.to_dict()
    assert loaded.tolerances == DEFAULT_TOLERANCES
    # committed collectives become the allowed kinds
    coll_rule = next(
        r for r in loaded.rules_for("step") if isinstance(r, CollectiveBudget)
    )
    assert coll_rule.allow == ("all-reduce",)
    assert loaded.rules_for("nope") is None


def test_budget_tolerance_boundary_is_inclusive():
    bf = BudgetFile.from_profiles("toy", _profiles())
    cap = allowed_max(1e9, "flops", bf.tolerances)
    assert cap == 1e9 * 1.1  # relative tolerance dominates the slack floor
    flop_rule = next(
        r for r in bf.rules_for("fwd") if isinstance(r, FlopBudget)
    )
    at_cap = _stub_program(CostProfile(flops=cap))
    assert flop_rule.check(at_cap) == []
    over = _stub_program(CostProfile(flops=cap * 1.001))
    assert len(flop_rule.check(over)) == 1


def test_budget_slack_floor_covers_near_zero_baselines():
    # 10% of 1 kFLOP is noise-level; the absolute floor absorbs it
    assert allowed_max(1e3, "flops", DEFAULT_TOLERANCES) == 1e3 + 1e6
    # ici/dcn get NO slack: committed zero collectives stay exactly zero
    assert allowed_max(0.0, "ici_bytes", DEFAULT_TOLERANCES) == 0.0


def test_diff_profiles_statuses():
    bf = BudgetFile.from_profiles("toy", _profiles())
    current = {
        "fwd": CostProfile(flops=3e9, hbm_bytes=2e9, peak_bytes=1e8),
        "step": _profiles()["step"],
    }
    by_key = {
        (d.program, d.metric): d.status for d in diff_profiles(bf, current)
    }
    assert by_key[("fwd", "flops")] == "regression"
    assert by_key[("fwd", "hbm_bytes")] == "ok"
    assert by_key[("fwd", "peak_bytes")] == "improvement"
    assert all(
        v == "ok" for (p, _), v in by_key.items() if p == "step"
    )


def test_budget_structural_findings():
    bf = BudgetFile.from_profiles("toy", _profiles())
    mismatched = {
        "fwd": _profiles()["fwd"],
        # committed at 4 partitions, now compiled for 1
        "step": CostProfile(flops=4e9, num_partitions=1),
        "brand_new": CostProfile(),
    }
    found = bf.structural_findings(mismatched)
    msgs = {f.program: f.message for f in found}
    assert "brand_new" in msgs and "no committed budget" in msgs["brand_new"]
    assert "step" in msgs and "num_partitions" in msgs["step"]
    assert all(f.severity == "error" and f.rule == "budget-file" for f in found)

    del bf.programs["fwd"]
    bf.programs["ghost"] = bf.programs["step"]
    stale = bf.structural_findings({"step": _profiles()["step"]})
    assert any("ghost" in f.message and "stale" in f.message for f in stale)


# --- the CLI gate ------------------------------------------------------------


def _run_cli(args, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--jaxpr-only",
         "--config", "dlrm_criteo_reduced", *args],
        capture_output=True, text=True, env=env, timeout=900,
    )


@pytest.mark.slow
def test_cli_budget_gate_roundtrip_and_doctored_regression(tmp_path):
    path = str(tmp_path / "reduced.json")
    report = str(tmp_path / "cost.json")

    # 1. regenerate: writes the file, exits 0
    res = _run_cli(["--update-budgets", "--budgets", path], tmp_path)
    assert res.returncode == 0, res.stderr[-3000:]
    committed = json.load(open(path))
    assert set(committed["programs"]) == {
        "fwd", "grad", "train_step", "train_step_telemetry", "serve_lookup",
        "serve_dlrm_cold", "serve_dlrm_hit",
    }

    # 2. clean gate: current == committed, exits 0, diff all-ok
    res = _run_cli(["--budgets", path, "--cost-report", report], tmp_path)
    assert res.returncode == 0, res.stderr[-3000:]
    diffs = json.load(open(report))["diffs"]
    assert diffs and all(d["status"] == "ok" for d in diffs)

    # 3. doctored budget: halve the committed bytes -> current is a 2x
    #    regression -> structured diff + non-zero exit
    committed["programs"]["fwd"]["hbm_bytes"] /= 2.0
    with open(path, "w") as fh:
        json.dump(committed, fh)
    res = _run_cli(["--budgets", path, "--cost-report", report], tmp_path)
    assert res.returncode == 1, res.stderr[-3000:]
    assert "[bytes-budget] fwd" in res.stderr
    bad = [d for d in json.load(open(report))["diffs"] if d["status"] != "ok"]
    assert len(bad) == 1
    assert bad[0]["program"] == "fwd"
    assert bad[0]["metric"] == "hbm_bytes"
    assert bad[0]["status"] == "regression"
    assert bad[0]["committed"] == committed["programs"]["fwd"]["hbm_bytes"]
    assert bad[0]["rel_change"] == pytest.approx(1.0)

    # 4. missing budget file is its own exit code (2): the gate cannot
    #    silently pass when there is nothing to gate against
    res = _run_cli(["--budgets", str(tmp_path / "missing.json")], tmp_path)
    assert res.returncode == 2


# --- the sharded bundle under a forced 4-device mesh ------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
from repro.analysis.audit import run_audit

report = run_audit("dlrm_criteo_reduced_sharded", with_cost=True)
out = {
    "ok": report.ok,
    "profiles": {n: p.to_dict() for n, p in report.profiles.items()},
    "findings": [f.to_dict() for f in report.findings],
}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_transition_audit_on_forced_mesh():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"], out["findings"]
    profs = out["profiles"]
    assert set(profs) == {
        "cluster_sharded", "assign_all_sharded", "train_step_sharded",
        "train_step_sharded_telemetry",
    }
    for prof in profs.values():
        assert prof["num_partitions"] == 4
        assert prof["dcn_bytes"] == 0.0
        assert set(prof["collectives"]) <= {
            "all-to-all", "all-reduce", "all-gather", "collective-permute",
        }
    # the distributed k-means really does psum
    assert profs["cluster_sharded"]["collectives"].get("all-reduce", 0) > 0
    # the model-parallel step really does route ids shard-to-shard
    assert profs["train_step_sharded"]["collectives"].get("all-to-all", 0) > 0
