"""Planted-violation suite for repro.analysis.

Every shipped rule gets (a) a deliberately broken toy program it MUST
flag and (b) a clean program it MUST pass — the rules are the CI gate,
so the gate itself is what's under test here.  Plus: walker traversal
through scan/cond sub-jaxprs, the AST source rules on tmp files, the
CLI exit-code contract, and an integration run of the real reduced
DLRM audit bundle.
"""
import json
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    RULES,
    AuditProgram,
    ConstantCapture,
    DeadInput,
    DonationCoverage,
    DtypeHygiene,
    LaunchBudget,
    NoDeviceGatherOf,
    NoHostCallback,
    NoTransfers,
    count_primitive,
    register,
    used_var_ids,
    walk,
)
from repro.analysis.rules import _is_real_transfer
from repro.analysis.source_rules import check_source_file, run_source_rules
from repro.compat import pallas as pl


def _launch(x):
    """One tiny pallas launch (interpret mode — jaxpr structure is what
    the rules audit, not the backend)."""

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True,
    )(x)


def _capture(fn, *args, **kw):
    return AuditProgram.capture(fn, *args, name="toy", **kw)


X = jnp.ones((8,), jnp.float32)


# --- registry ---------------------------------------------------------------


def test_registry_has_every_shipped_rule():
    import repro.analysis.cost_rules  # noqa: F401 — registers the cost rules

    assert set(RULES) == {
        "launch-budget", "no-device-gather", "donation-coverage",
        "dtype-hygiene", "no-host-callback", "no-transfers",
        "constant-capture", "dead-input",
        "flop-budget", "bytes-budget", "peak-memory-budget",
        "collective-budget", "no-replicated-param",
    }


def test_registry_rejects_duplicates_and_missing_ids():
    with pytest.raises(ValueError, match="duplicate"):
        register(type("Fake", (), {"id": "launch-budget"}))
    with pytest.raises(ValueError, match="no id"):
        register(type("Anon", (), {"id": ""}))


# --- walker -----------------------------------------------------------------


def test_walker_recurses_into_scan_and_cond():
    def scanned(x):
        def body(c, _):
            return _launch(c), None

        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    closed = jax.make_jaxpr(scanned)(X)
    assert count_primitive(closed, "pallas_call") == 1  # ONE eqn, 3 trips
    paths = [s.path for s in walk(closed) if s.primitive == "pallas_call"]
    assert len(paths) == 1 and "scan" in paths[0]  # found INSIDE the body

    def conded(x):
        return jax.lax.cond(x[0] > 0, _launch, lambda v: v, x)

    assert count_primitive(jax.make_jaxpr(conded)(X), "pallas_call") == 1


def test_used_var_ids_exact_for_top_level_invars():
    closed = jax.make_jaxpr(lambda a, b: a * 2.0)(X, X)
    used = used_var_ids(closed, include_outputs=False)
    a_var, b_var = closed.jaxpr.invars
    assert id(a_var) in used and id(b_var) not in used


# --- LaunchBudget -----------------------------------------------------------


def test_launch_budget_flags_extra_launch():
    assert LaunchBudget(1).check(_capture(_launch, X)) == []
    found = LaunchBudget(1).check(_capture(lambda x: _launch(_launch(x)), X))
    assert len(found) == 1 and found[0].rule == "launch-budget"
    assert "2 pallas_call" in found[0].message
    assert "pallas_call" in found[0].where  # points at the extra site


def test_launch_budget_exact_flags_missing_launch():
    # exact=True also catches the launch DISAPPEARING (fusion regressed
    # to a pure-XLA gather without anyone noticing)
    found = LaunchBudget(1).check(_capture(lambda x: x + 1.0, X))
    assert len(found) == 1 and "0 pallas_call" in found[0].message
    assert LaunchBudget(1, exact=False).check(_capture(lambda x: x + 1.0, X)) == []


# --- NoDeviceGatherOf -------------------------------------------------------


def test_no_device_gather_flags_consumed_pointer_input():
    tree = {"ptr": jnp.zeros((4,), jnp.int32), "w": X}
    rule = NoDeviceGatherOf(("ptr",))
    assert rule.check(_capture(lambda d: d["w"] * 2.0, tree)) == []
    found = rule.check(
        _capture(lambda d: d["w"] + d["ptr"].astype(jnp.float32).sum(), tree)
    )
    assert len(found) == 1 and "'ptr'" in found[0].where


def test_capture_sees_through_prejitted_fn():
    # make_jaxpr wraps an already-jitted fn in one jit equation fed every
    # input; the capture hands rules the body, where ptr is never read
    tree = {"ptr": jnp.zeros((4,), jnp.int32), "w": X}
    prog = _capture(jax.jit(lambda d: d["w"] * 2.0), tree)
    assert NoDeviceGatherOf(("ptr",)).check(prog) == []
    assert len(DeadInput().check(prog)) == 1


def test_no_device_gather_refuses_vacuous_pass():
    # no input named ptr at all -> the spec is mislabeled, not "clean"
    found = NoDeviceGatherOf(("ptr",)).check(_capture(lambda d: d["w"], {"w": X}))
    assert len(found) == 1 and "vacuous" in found[0].message


# --- DonationCoverage -------------------------------------------------------


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donation_coverage_passes_aliased_and_flags_unaliased():
    state = {"a": X, "b": jnp.zeros((3,), jnp.float32)}
    good = _capture(
        lambda s: {k: v + 1.0 for k, v in s.items()},
        state, donate_argnums=(0,),
    )
    assert DonationCoverage().check(good) == []

    # output shapes match nothing -> XLA can alias no donated buffer
    bad = _capture(lambda s: s["a"].sum(), state, donate_argnums=(0,))
    found = DonationCoverage().check(bad)
    assert len(found) == 1 and "2 leaves donated" in found[0].message


def test_donation_coverage_refuses_undonated_program():
    found = DonationCoverage().check(_capture(lambda s: s, {"a": X}))
    assert len(found) == 1 and "donates nothing" in found[0].message


# --- DtypeHygiene -----------------------------------------------------------


def test_dtype_hygiene_flags_f64():
    assert DtypeHygiene().check(_capture(lambda x: x * 2.0, X)) == []
    with jax.enable_x64(True):
        bad = _capture(
            lambda x: x * 2.0, jax.ShapeDtypeStruct((4,), jnp.float64)
        )
    found = DtypeHygiene().check(bad)
    assert found and all(f.rule == "dtype-hygiene" for f in found)
    assert "float64" in found[0].message


# --- NoHostCallback ---------------------------------------------------------


def test_no_host_callback_flags_pure_callback():
    def with_cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    found = NoHostCallback().check(_capture(with_cb, X))
    assert len(found) == 1 and "pure_callback" in found[0].message
    assert NoHostCallback().check(_capture(lambda x: x * 2.0, X)) == []


# --- NoTransfers ------------------------------------------------------------


def test_no_transfers_flags_concrete_placement():
    cpu0 = jax.devices("cpu")[0]
    found = NoTransfers().check(
        _capture(lambda x: jax.device_put(x, cpu0) + 1.0, X)
    )
    assert len(found) == 1 and found[0].rule == "no-transfers"


def test_no_transfers_ignores_alias_noop_and_fails_closed():
    class Sem:
        def __str__(self):
            return "CopySemantics.ALIAS"

    benign = types.SimpleNamespace(
        params={"devices": [None], "srcs": [None], "copy_semantics": [Sem()]}
    )
    assert not _is_real_transfer(benign)
    placed = types.SimpleNamespace(
        params={"devices": ["cpu:0"], "srcs": [None], "copy_semantics": [Sem()]}
    )
    assert _is_real_transfer(placed)
    # unknown param shape (jax drift) must flag, not silently pass
    assert _is_real_transfer(types.SimpleNamespace(params={}))


# --- ConstantCapture --------------------------------------------------------


def test_constant_capture_flags_large_baked_const():
    big = jnp.arange(1 << 15, dtype=jnp.float32)  # 128 KiB, closed over
    found = ConstantCapture(max_bytes=1 << 16).check(
        _capture(lambda x: x + big.sum(), X)
    )
    assert len(found) == 1 and "pass it as an argument" in found[0].message

    small = jnp.arange(8, dtype=jnp.float32)
    assert ConstantCapture(max_bytes=1 << 16).check(
        _capture(lambda x: x + small.sum(), X)
    ) == []


# --- DeadInput --------------------------------------------------------------


def test_dead_input_flags_unconsumed_leaf_unless_allowed():
    tree = {"a": X, "b": jnp.zeros((3,), jnp.float32)}
    found = DeadInput().check(_capture(lambda d: d["a"] * 2.0, tree))
    assert len(found) == 1 and "'b'" in found[0].where
    assert DeadInput(allow=("b",)).check(
        _capture(lambda d: d["a"] * 2.0, tree)
    ) == []
    # passing an input through to the output counts as consumption
    assert DeadInput().check(_capture(lambda d: d, tree)) == []


# --- AST source rules -------------------------------------------------------


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_fuse_rows_twin_rule(tmp_path):
    bad = _write(tmp_path, "bad.py", """
        class T:
            def fuse_rows(self, ids):
                return ids
    """)
    assert [f.rule for f in check_source_file(bad)] == ["fuse-rows-twin"]
    good = _write(tmp_path, "good.py", """
        class T:
            def fuse_rows(self, ids):
                return ids

            def fuse_rows_np(self, ids):
                return ids
    """)
    assert check_source_file(good) == []


def test_int_cast_rule_scoped_to_jax_modules(tmp_path):
    bad = _write(tmp_path, "bad.py", """
        import jax.numpy as jnp

        def f(x):
            return int(x.sum()), x.max().item()
    """)
    assert [f.rule for f in check_source_file(bad)] == [
        "no-int-cast", "no-int-cast",
    ]
    # identical code in a pure-numpy module holds no traced values
    pure = _write(tmp_path, "pure.py", """
        import numpy as np

        def f(x):
            return int(x.sum()), x.max().item()
    """)
    assert check_source_file(pure) == []
    waived = _write(tmp_path, "waived.py", """
        import jax

        def f(x):
            return int(x.sum())  # audit: allow-int-cast
    """)
    assert check_source_file(waived) == []


def test_stale_waiver_is_itself_a_finding(tmp_path):
    # the excused int() was removed but the waiver stayed behind
    stale = _write(tmp_path, "stale.py", """
        import jax

        def f(x):
            return x.sum()  # audit: allow-int-cast
    """)
    found = check_source_file(stale)
    assert [f.rule for f in found] == ["stale-waiver"]
    assert "allow-int-cast" in found[0].message
    # a misspelled tag suppresses nothing AND is called out as unknown
    typo = _write(tmp_path, "typo.py", """
        import jax

        def f(x):
            return int(x.sum())  # audit: allow-int-casts
    """)
    rules = sorted(f.rule for f in check_source_file(typo))
    assert rules == ["no-int-cast", "stale-waiver"]
    assert any("unknown tag" in f.message for f in check_source_file(typo))


def test_waiver_text_inside_strings_is_inert(tmp_path):
    # prose about waivers (docstrings, messages) is neither a suppression
    # nor stale — only COMMENT tokens count
    doc = _write(tmp_path, "doc.py", '''
        import jax

        def f(x):
            """Host-side casts need `# audit: allow-int-cast` waivers."""
            return x.sum()
    ''')
    assert check_source_file(doc) == []
    # ...and a string does NOT suppress a real finding on its line
    inline = _write(tmp_path, "inline.py", """
        import jax

        def f(x):
            return int(x.sum()), "audit: allow-int-cast"
    """)
    assert [f.rule for f in check_source_file(inline)] == ["no-int-cast"]


def test_raw_experimental_rule_excepts_compat(tmp_path):
    bad = _write(tmp_path, "bad.py", """
        from jax.experimental import pallas as pl
    """)
    assert [f.rule for f in check_source_file(bad)] == ["no-raw-experimental"]
    compat = _write(tmp_path, "compat.py", """
        from jax.experimental import pallas as pl
    """)
    assert check_source_file(compat) == []
    shimmed = _write(tmp_path, "shimmed.py", """
        from repro.compat import pallas as pl
    """)
    assert check_source_file(shimmed) == []


def test_source_rules_walk_and_syntax_finding(tmp_path):
    _write(tmp_path, "broken.py", "def f(:\n")
    _write(tmp_path, "ok.py", "x = 1\n")
    found = run_source_rules(str(tmp_path))
    assert [f.rule for f in found] == ["syntax"]


def test_repo_source_tree_is_clean():
    assert run_source_rules("src/repro") == []


# --- integration: the real audit bundle + CLI -------------------------------


def test_reduced_dlrm_audit_is_green():
    from repro.analysis import run_audit

    report = run_audit("dlrm_criteo_reduced")
    assert report.ok, report.to_json()
    assert [p["name"] for p in report.programs] == [
        "fwd", "grad", "train_step", "train_step_telemetry", "serve_lookup",
        "serve_dlrm_cold", "serve_dlrm_hit",
    ]
    # the report records the launch counts the budgets pinned
    by_name = {p["name"]: p for p in report.programs}
    assert by_name["fwd"]["n_eqns_by_primitive"]["pallas_call"] == 1
    assert by_name["train_step"]["n_eqns_by_primitive"]["pallas_call"] == 2
    # telemetry is free: same launch count as the bare step
    assert (
        by_name["train_step_telemetry"]["n_eqns_by_primitive"]["pallas_call"]
        == 2
    )
    # serve: ONE fused launch on the cold path, ZERO on a fully-hit batch
    assert (
        by_name["serve_dlrm_cold"]["n_eqns_by_primitive"]["pallas_call"] == 1
    )
    assert (
        by_name["serve_dlrm_hit"]["n_eqns_by_primitive"].get("pallas_call", 0)
        == 0
    )


def test_cli_source_only_exit_codes(tmp_path):
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "m.py").write_text("x = 1\n")
    dirty = tmp_path / "dirty"
    dirty.mkdir()
    (dirty / "m.py").write_text("from jax.experimental import pallas\n")

    import os

    import repro.analysis as _mod

    src_dir = os.path.dirname(os.path.dirname(os.path.dirname(_mod.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")

    def run(root):
        out = tmp_path / "report.json"
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--source-only",
             "--source-root", str(root), "--json", str(out)],
            capture_output=True, text=True, env=env,
        )
        return proc, json.loads(out.read_text())

    proc, rep = run(clean)
    assert proc.returncode == 0 and rep["ok"] is True
    proc, rep = run(dirty)
    assert proc.returncode == 1 and rep["ok"] is False
    assert rep["source_findings"][0]["rule"] == "no-raw-experimental"
