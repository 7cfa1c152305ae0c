"""Per-entry-point audit specs + the report the CLI/CI gate consumes.

An ``AuditSpec`` bundles one jitted entry point (built lazily, captured
ABSTRACTLY — ``jax.eval_shape`` + ``ShapeDtypeStruct`` inputs, so the
full Criteo config audits without allocating its 33M-row pointer tables)
with the rule instances that encode its invariants.  ``run_audit`` runs
a named config's whole bundle and returns a ``Report`` that serializes
to ``AUDIT_report.json`` and carries the CI exit code.

The ``dlrm_criteo`` bundle audits the canonical programs:

  * ``fwd``          — DLRM forward: ONE pallas launch, clean dtypes,
                       no callbacks/transfers/large consts.
  * ``grad``         — loss gradient: exactly TWO launches (fwd + the
                       transposed one-hot scatter-add bwd).
  * ``train_step``   — the donated step WITH the in-step sketch counter:
                       still two launches (sketch tracking adds zero
                       dispatches), every TrainState leaf aliased to an
                       output, nothing dead but the transition-only
                       ``epoch`` counters.
  * ``train_step_telemetry`` — the same step with ``repro.obs`` in-step
                       health metrics on: identical launch budget,
                       donation coverage, and no-callback invariants —
                       the gate that proves the instrumentation free.
  * ``serve_lookup`` — the host-translated inference lookup: one launch
                       and ZERO reads of the ptr/hs pointer tables
                       (DESIGN.md §4's pod contract).

The ``*_sharded`` bundles audit the distributed entry points: the CCE
transition (``cluster_sharded`` / ``assign_all_sharded`` over a mesh
spanning every visible device — zero pallas launches, pointer operands
entering id-SHARDED) and the model-parallel train step
(``train_step_sharded``: supertable + moments codebook-sharded, batch
ids routed by all-to-all — see ``launch.steps.build_dlrm_train_step``).
Each carries a ``CollectiveBudget`` naming exactly which ICI collective
kinds it may emit (and pinning DCN traffic to zero) plus
``NoReplicatedParam`` at ERROR severity: since ROADMAP item 1 landed, no
O(vocab) leaf may enter any sharded program replicated.

Cost rules (``spec.cost_rules``) are separate from structural rules:
they AOT-compile the entry point (seconds per program instead of
milliseconds), so ``run_audit`` only runs them — and only then computes
``CostProfile``s — when asked (``with_cost=True`` / ``--budgets``).

ROADMAP items 1–3 (sharded supertable, serve engine, quantized slabs)
should land by ADDING specs here — their invariants become checkable
before the systems are built.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Callable

from repro.analysis.cost_rules import CollectiveBudget, NoReplicatedParam, cost_profile
from repro.analysis.program import AuditProgram
from repro.analysis.rules import (
    ConstantCapture,
    DeadInput,
    DonationCoverage,
    DtypeHygiene,
    Finding,
    LaunchBudget,
    NoDeviceGatherOf,
    NoHostCallback,
    NoTransfers,
    Rule,
    audit_program,
)
from repro.analysis.walker import primitive_counts

# epoch is the CCE transition counter: it must RIDE the dynamic buffers
# (PR 1 — a static leaf would freeze the transition schedule into the
# program) but no lookup/step program reads it — dead by contract.
_EPOCH_ALLOW = ("epoch",)

_HYGIENE: tuple[Rule, ...] = (
    DtypeHygiene(),
    NoHostCallback(),
    NoTransfers(),
    ConstantCapture(),
)


@dataclasses.dataclass(frozen=True)
class AuditSpec:
    """One entry point: a thunk building the captured program (lazy —
    building traces/loads jax) plus the rules that must hold on it.

    ``rules`` run on every audit (jaxpr/lowering only — cheap);
    ``cost_rules`` additionally AOT-compile the program and only run
    under ``run_audit(..., with_cost=True)``."""

    name: str
    build: Callable[[], AuditProgram]
    rules: tuple[Rule, ...]
    cost_rules: tuple[Rule, ...] = ()


def _abstract_dlrm(cfg):
    """(params, buffers) ShapeDtypeStruct trees — zero allocation."""
    import jax

    from repro.models import dlrm

    return jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))


def _batch_struct(cfg, batch_size: int, *, label: bool):
    import jax
    import jax.numpy as jnp

    batch = {
        "dense": jax.ShapeDtypeStruct((batch_size, cfg.n_dense), jnp.float32),
        "sparse": jax.ShapeDtypeStruct((batch_size, cfg.n_sparse), jnp.int32),
    }
    if label:
        batch["label"] = jax.ShapeDtypeStruct((batch_size,), jnp.float32)
    return batch


def _build_fwd(cfg, batch_size):
    from repro.models import dlrm

    params, buffers = _abstract_dlrm(cfg)
    batch = _batch_struct(cfg, batch_size, label=False)
    return AuditProgram.capture(
        lambda p, b, bt: dlrm.forward(p, b, cfg, bt),
        params, buffers, batch, name="fwd",
    )


def _build_grad(cfg, batch_size):
    import jax

    from repro.models import dlrm

    params, buffers = _abstract_dlrm(cfg)
    batch = _batch_struct(cfg, batch_size, label=True)
    return AuditProgram.capture(
        lambda p, b, bt: jax.grad(
            lambda q: dlrm.bce_loss(q, b, cfg, bt)
        )(p),
        params, buffers, batch, name="grad",
    )


def _build_train_step(cfg, batch_size, stream_cfg, *, telemetry=False):
    import jax

    from repro.models import dlrm
    from repro.optim import sgd
    from repro.stream import make_step_cell_counter
    from repro.train.loop import init_state, make_train_step, split_buffers

    import jax.numpy as jnp

    params, buffers = _abstract_dlrm(cfg)
    dyn, static = split_buffers(buffers)
    opt = sgd(momentum=0.9)

    def loss_fn(p, b, mb):
        return dlrm.bce_loss(p, b, cfg, mb), {}

    sketch_fn = None
    if stream_cfg is not None:
        sketch_fn = make_step_cell_counter(dlrm.make_id_tracker(cfg, stream_cfg))
    tcfg = None
    if telemetry:
        from repro.obs.telemetry import TelemetryConfig

        tcfg = TelemetryConfig()
    step = make_train_step(
        loss_fn, opt, lambda s: jnp.float32(0.05), static,
        sketch_fn=sketch_fn, telemetry=tcfg, donate=True,
    )
    state = jax.eval_shape(lambda: init_state(params, opt, dyn))
    batch = {
        k: jax.ShapeDtypeStruct((1, *v.shape), v.dtype)
        for k, v in _batch_struct(cfg, batch_size, label=True).items()
    }
    return AuditProgram.capture(
        step, state, batch,
        name="train_step_telemetry" if telemetry else "train_step",
        donate_argnums=(0,),
    )


def _build_serve_lookup(cfg, batch_size):
    import jax
    import jax.numpy as jnp

    coll = cfg.collection
    params, buffers = _abstract_dlrm(cfg)
    rows = jax.ShapeDtypeStruct(
        (batch_size, coll.rows_n_cols, coll.rows_n_tables), jnp.int32
    )
    return AuditProgram.capture(
        lambda p, b, r: coll.lookup_all(p, b, None, use_kernel=True, rows=r),
        params["emb"], buffers["emb"], rows, name="serve_lookup",
    )


def _build_serve_dlrm(cfg, batch_size, *, cold: bool, cache_slots: int = 4096):
    """The serve engine's two programs (serve/dlrm.py, DESIGN.md §11).

    ``cold=False`` is the fully-cache-hit batch: every embedding answered
    by the hot-cache gather, the supertable never enters the program —
    LaunchBudget(0) makes "a hit batch skips the launch" structural.
    ``cold=True`` is the mixed batch: cache gather + ONE fused launch over
    the compacted cold sub-batch on host-translated rows; the emb buffers
    ride along so NoDeviceGatherOf has real ptr/hs inputs to clear (a
    vacuous pass is itself a finding)."""
    import jax
    import jax.numpy as jnp

    from repro.serve.dlrm import make_serve_fns

    coll = cfg.collection
    params, buffers = _abstract_dlrm(cfg)
    hit_fn, cold_fn = make_serve_fns(cfg, use_kernel=True)
    cache_tab = jax.ShapeDtypeStruct((cache_slots, cfg.emb_dim), jnp.float32)
    slots = jax.ShapeDtypeStruct((batch_size, cfg.n_sparse), jnp.int32)
    dense = jax.ShapeDtypeStruct((batch_size, cfg.n_dense), jnp.float32)
    if not cold:
        mlp = {"bottom": params["bottom"], "top": params["top"]}
        return AuditProgram.capture(
            hit_fn, mlp, cache_tab, slots, dense, name="serve_dlrm_hit",
        )
    rows = jax.ShapeDtypeStruct(
        (batch_size, coll.rows_n_cols, coll.rows_n_tables), jnp.int32
    )
    cold_idx = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
    return AuditProgram.capture(
        cold_fn, params, buffers["emb"], cache_tab, slots, dense, rows,
        cold_idx, name="serve_dlrm_cold",
    )


def dlrm_audits(cfg, stream_cfg=None, *, batch_size: int = 32):
    """The canonical DLRM audit bundle for any DLRMConfig."""
    # the 1-device contract is ZERO collectives in every compiled module —
    # the default CollectiveBudget allows nothing
    no_collectives = (CollectiveBudget(),)
    return (
        AuditSpec(
            "fwd",
            lambda: _build_fwd(cfg, batch_size),
            (LaunchBudget(1), DeadInput(allow=_EPOCH_ALLOW), *_HYGIENE),
            cost_rules=no_collectives,
        ),
        AuditSpec(
            "grad",
            lambda: _build_grad(cfg, batch_size),
            (LaunchBudget(2), *_HYGIENE),
            cost_rules=no_collectives,
        ),
        AuditSpec(
            "train_step",
            lambda: _build_train_step(cfg, batch_size, stream_cfg),
            (
                LaunchBudget(2),
                DonationCoverage(),
                DeadInput(allow=_EPOCH_ALLOW),
                *_HYGIENE,
            ),
            cost_rules=no_collectives,
        ),
        # the telemetry-enabled step carries the SAME invariants as the
        # bare one — in-step health metrics (repro.obs) are pure jnp
        # reductions that must not add launches, break donation, or
        # smuggle in a host callback.  This spec is what makes "the
        # instrumentation is free" a gated claim rather than a comment.
        AuditSpec(
            "train_step_telemetry",
            lambda: _build_train_step(
                cfg, batch_size, stream_cfg, telemetry=True
            ),
            (
                LaunchBudget(2),
                DonationCoverage(),
                DeadInput(allow=_EPOCH_ALLOW),
                *_HYGIENE,
            ),
            cost_rules=no_collectives,
        ),
        AuditSpec(
            "serve_lookup",
            lambda: _build_serve_lookup(cfg, batch_size),
            (
                LaunchBudget(1),
                NoDeviceGatherOf(("ptr", "hs")),
                DeadInput(allow=("ptr", "hs", *_EPOCH_ALLOW)),
                *_HYGIENE,
            ),
            cost_rules=no_collectives,
        ),
        # the serve engine's cold path: hot-cache gather + ONE fused
        # launch over the compacted cold sub-batch, no ptr/hs gathers
        AuditSpec(
            "serve_dlrm_cold",
            lambda: _build_serve_dlrm(cfg, batch_size, cold=True),
            (
                LaunchBudget(1),
                NoDeviceGatherOf(("ptr", "hs")),
                DeadInput(allow=("ptr", "hs", *_EPOCH_ALLOW)),
                *_HYGIENE,
            ),
            cost_rules=no_collectives,
        ),
        # the fully-cache-hit path: ZERO heavy launches — the supertable
        # is not even an input to the program
        AuditSpec(
            "serve_dlrm_hit",
            lambda: _build_serve_dlrm(cfg, batch_size, cold=False),
            (LaunchBudget(0), DeadInput(), *_HYGIENE),
            cost_rules=no_collectives,
        ),
    )


# --- the sharded CCE-transition bundle ----------------------------------


def _largest_cce(cfg):
    """The config's largest CCE table — the one whose transition cost
    dominates (the full-vocab assignment is O(d1))."""
    from repro.core.cce import CCE

    tables = [
        t for t in (cfg.table(i) for i in range(cfg.n_sparse))
        if isinstance(t, CCE)
    ]
    if not tables:
        raise SystemExit(
            "sharded audit config needs at least one CCE table; "
            f"emb_method={cfg.emb_method!r}"
        )
    return max(tables, key=lambda t: t.d1)


def _abstract_cce_state(table):
    """(params, buffers) ShapeDtypeStructs for one CCE table, built by
    hand: ``init_buffers`` does real numpy work that is O(d1) (~0.5 GB at
    Criteo scale), and the audit must stay allocation-free."""
    import jax
    import jax.numpy as jnp

    params = {
        "tables": jax.ShapeDtypeStruct(
            (table.c, 2, table.k, table.dsub), table.dtype
        ),
    }
    buffers = {
        "ptr": jax.ShapeDtypeStruct((table.c, table.d1), jnp.int32),
        "hs": jax.ShapeDtypeStruct((table.c, 2), jnp.uint32),
        "epoch": jax.ShapeDtypeStruct((), jnp.int32),
    }
    return params, buffers


def _data_mesh():
    """1-axis mesh over every visible device (the multi-device CI lane
    forces 4 host devices via XLA_FLAGS)."""
    import jax

    from repro.launch.mesh import DATA_AXIS, make_mesh

    return make_mesh((jax.device_count(),), (DATA_AXIS,))


def _cce_shardings(mesh, table):
    """Input shardings for the transition entry points: the (c, d1)
    pointer table enters SHARDED at its at-rest layout
    (``mesh.ptr_partition_spec`` — id axis when the vocab divides, column
    axis for Criteo's ragged vocabs), everything else replicated.
    Pre-jitting the capture with these is what lets ``NoReplicatedParam``
    run at error severity — an audit that handed the programs replicated
    pointers would flag its own harness."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import DATA_AXIS, ptr_partition_spec

    def ns(spec):
        return NamedSharding(mesh, spec)

    nsh = mesh.shape[DATA_AXIS]
    params_sh = {"tables": ns(P())}
    buffers_sh = {
        "ptr": ns(ptr_partition_spec(table.c, table.d1, nsh, DATA_AXIS)),
        "hs": ns(P()),
        "epoch": ns(P()),
    }
    return jax, ns, params_sh, buffers_sh


def _build_cluster_sharded(cfg):
    import jax.numpy as jnp

    table = _largest_cce(cfg)
    mesh = _data_mesh()
    params, buffers = _abstract_cce_state(table)
    jax, ns, params_sh, buffers_sh = _cce_shardings(mesh, table)
    from jax.sharding import PartitionSpec as P

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    chunk = cfg.emb_cluster_chunk or None
    jitted = jax.jit(
        lambda k, p, b: table.cluster_sharded(
            k, p, b, mesh, chunk_size=chunk, use_kernel=False
        ),
        in_shardings=(ns(P()), params_sh, buffers_sh),
    )
    return AuditProgram.capture(
        jitted, key, params, buffers, name="cluster_sharded",
    )


def _build_assign_all_sharded(cfg):
    import jax.numpy as jnp

    table = _largest_cce(cfg)
    mesh = _data_mesh()
    params, buffers = _abstract_cce_state(table)
    jax, ns, params_sh, buffers_sh = _cce_shardings(mesh, table)
    from jax.sharding import PartitionSpec as P

    centroids = jax.ShapeDtypeStruct(
        (table.c, table.k, table.dsub), jnp.float32
    )
    chunk = cfg.emb_cluster_chunk or None
    jitted = jax.jit(
        lambda p, b, cen: table.assign_all_sharded(
            p, b, cen, mesh, chunk_size=chunk, use_kernel=False
        ),
        in_shardings=(params_sh, buffers_sh, ns(P())),
    )
    return AuditProgram.capture(
        jitted, params, buffers, centroids, name="assign_all_sharded",
    )


def _build_train_step_sharded(cfg, *, telemetry=False):
    """The model-parallel DLRM train step over a (1, n_devices) mesh —
    the slab/moments/ptr enter sharded per ``dlrm_state_specs``, batch
    ids arrive host-translated and pre-bucketed, and the id routing runs
    as in-step all-to-all.  With ``telemetry`` the in-step health metrics
    (including the per-shard routing-occupancy skew read off the
    pre-bucketed rows) ride the same program."""
    import dataclasses as _dc

    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_dlrm_train_step
    from repro.optim import sgd

    n = len(jax.devices())
    mesh = make_host_mesh(data=1, model=n)
    cfg = _dc.replace(cfg, emb_k_multiple=n)
    tcfg = None
    if telemetry:
        from repro.obs.telemetry import TelemetryConfig

        tcfg = TelemetryConfig()
    jitted, (state_shape, batch_struct), _ = build_dlrm_train_step(
        cfg, mesh, batch_size=32, accum=1, optimizer=sgd(momentum=0.9),
        telemetry=tcfg,
    )
    return AuditProgram.capture(
        jitted, state_shape, batch_struct,
        name="train_step_sharded_telemetry" if telemetry
        else "train_step_sharded",
        donate_argnums=(0,),
    )


def dlrm_sharded_audits(cfg):
    """Audit bundle for the distributed CCE entry points.

    The byte caps here are deliberately loose (the committed budget file
    supplies the tight, config-specific numbers); what the spec-level
    ``CollectiveBudget`` pins is the *kinds*: all-reduce (the psum'd
    k-means moments), all-gather (the sharded pointer gathered where
    consumed), all-to-all (the step's batch-id routing, and the
    at-rest → id-sharded pointer reshard when a ragged vocab forces
    column-sharded storage — ``mesh.ptr_partition_spec``), plus
    collective-permute (XLA's lowering of halo/reshard moves inside the
    same patterns) — nothing else, and nothing over DCN.
    ``NoReplicatedParam`` runs at ERROR severity: every large slab (the
    supertable, its moments, the pointer table) must enter its program
    sharded, and a replicated copy reappearing anywhere fails the audit
    outright."""
    ici_collectives = CollectiveBudget(
        allow=(
            "all-to-all",
            "all-reduce",
            "all-gather",
            "collective-permute",
        ),
        max_ici_bytes=math.inf,
        max_dcn_bytes=0.0,
    )
    replication_debt = NoReplicatedParam()
    return (
        AuditSpec(
            "cluster_sharded",
            lambda: _build_cluster_sharded(cfg),
            (LaunchBudget(0), DeadInput(allow=_EPOCH_ALLOW), *_HYGIENE),
            cost_rules=(ici_collectives, replication_debt),
        ),
        AuditSpec(
            "assign_all_sharded",
            lambda: _build_assign_all_sharded(cfg),
            (
                LaunchBudget(0),
                DeadInput(allow=_EPOCH_ALLOW),
                *_HYGIENE,
            ),
            cost_rules=(ici_collectives, replication_debt),
        ),
        AuditSpec(
            "train_step_sharded",
            lambda: _build_train_step_sharded(cfg),
            (
                LaunchBudget(2),
                DonationCoverage(),
                NoDeviceGatherOf(("ptr", "hs")),
                DeadInput(allow=("ptr", "hs", *_EPOCH_ALLOW)),
                *_HYGIENE,
            ),
            cost_rules=(ici_collectives, replication_debt),
        ),
        # telemetry-enabled twin: the routing-skew/occupancy metrics must
        # not add launches, collectives kinds, callbacks, or replication
        AuditSpec(
            "train_step_sharded_telemetry",
            lambda: _build_train_step_sharded(cfg, telemetry=True),
            (
                LaunchBudget(2),
                DonationCoverage(),
                NoDeviceGatherOf(("ptr", "hs")),
                DeadInput(allow=("ptr", "hs", *_EPOCH_ALLOW)),
                *_HYGIENE,
            ),
            cost_rules=(ici_collectives, replication_debt),
        ),
    )


def _dlrm_criteo_specs():
    from repro.configs import dlrm_criteo

    return dlrm_audits(dlrm_criteo.CONFIG, dlrm_criteo.STREAM)


def _dlrm_criteo_reduced_specs():
    from repro.configs import dlrm_criteo

    return dlrm_audits(
        dlrm_criteo.reduced(emb_method="cce", cap=512),
        dlrm_criteo.reduced_stream(),
    )


def _dlrm_criteo_sharded_specs():
    from repro.configs import dlrm_criteo

    return dlrm_sharded_audits(dlrm_criteo.CONFIG)


def _dlrm_criteo_reduced_sharded_specs():
    from repro.configs import dlrm_criteo

    return dlrm_sharded_audits(dlrm_criteo.reduced(emb_method="cce", cap=512))


# config name -> thunk returning the spec tuple (thunks: importing a
# config loads jax; the CLI must stay importable without it)
AUDIT_CONFIGS: dict[str, Callable[[], tuple[AuditSpec, ...]]] = {
    "dlrm_criteo": _dlrm_criteo_specs,
    "dlrm_criteo_reduced": _dlrm_criteo_reduced_specs,
    "dlrm_criteo_sharded": _dlrm_criteo_sharded_specs,
    "dlrm_criteo_reduced_sharded": _dlrm_criteo_reduced_sharded_specs,
}


@dataclasses.dataclass
class Report:
    """One audit run: per-program rule coverage + structured findings
    (+ per-program ``CostProfile``s when the run captured cost)."""

    config: str
    programs: list[dict]
    findings: list[Finding]
    profiles: dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    def to_dict(self) -> dict:
        d = {
            "config": self.config,
            "ok": self.ok,
            "programs": self.programs,
            "findings": [f.to_dict() for f in self.findings],
        }
        if self.profiles:
            d["cost"] = {
                name: prof.to_dict() for name, prof in self.profiles.items()
            }
        return d

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=2, **kw)


def run_audit(config: str, *, with_cost: bool = False, budget=None) -> Report:
    """Build + audit every entry point of a named config.

    ``with_cost=True`` additionally AOT-compiles each entry point, runs
    its ``cost_rules``, and fills ``Report.profiles``.  ``budget`` (a
    ``budget.BudgetFile``) layers the committed budget's rules on top:
    per-metric caps at committed*(1+tol), plus structural findings for
    missing/stale entries and partition-count mismatches.
    """
    try:
        specs = AUDIT_CONFIGS[config]()
    except KeyError:
        raise SystemExit(
            f"unknown audit config {config!r}; have {sorted(AUDIT_CONFIGS)}"
        ) from None
    programs, findings, profiles = [], [], {}
    for spec in specs:
        prog = spec.build()
        rules = spec.rules
        if with_cost:
            rules = rules + spec.cost_rules
            if budget is not None and (
                budget_rules := budget.rules_for(spec.name)
            ):
                rules = rules + budget_rules
        found = audit_program(prog, rules)
        findings.extend(found)
        if with_cost:
            profiles[spec.name] = cost_profile(prog)
        programs.append({
            "name": spec.name,
            "rules": [r.id for r in rules],
            "n_findings": len(found),
            "n_eqns_by_primitive": {
                k: v for k, v in sorted(
                    primitive_counts(prog.closed).items()
                ) if k in ("pallas_call", "scan", "while", "cond", "jit")
            },
        })
    if with_cost and budget is not None:
        findings.extend(budget.structural_findings(profiles))
    return Report(
        config=config, programs=programs, findings=findings, profiles=profiles
    )
