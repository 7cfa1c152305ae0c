"""Capture a jitted entry point as an auditable program.

``AuditProgram.capture`` traces a callable to a closed jaxpr (abstract —
``jax.ShapeDtypeStruct`` args work, so the FULL Criteo config audits with
zero array allocation) and labels every flattened input variable with its
pytree path (``[1]['emb'][0][2]['ptr']``).  Rules then talk about inputs
by *name* — "the ptr buffers", "the donated state leaves" — instead of by
flat position, which is what makes audit specs declarative.

Lowering (for donation/aliasing rules) and AOT compilation (for the
quantitative cost rules — the compiled module is what ``launch/hlo_cost``
walks) are lazy and cached: tracing is milliseconds, lowering the full
train step is seconds, compiling it is tens of seconds, and most rules
only need the jaxpr.  Compilation is abstract end to end (AOT: lower +
compile on ShapeDtypeStructs) — no buffer is ever allocated.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax


def _tree_labels(args) -> tuple[str, ...]:
    flat = jax.tree_util.tree_leaves_with_path(args)
    return tuple(jax.tree_util.keystr(path) for path, _ in flat)


def label_matches(label: str, names: tuple[str, ...]) -> bool:
    """True when the pytree path ``label`` passes through a dict key in
    ``names`` (``[0]['emb'][1]['ptr']`` matches name ``ptr``)."""
    return any(re.search(rf"\['{re.escape(n)}'\]", label) for n in names)


def _unwrap_trivial_jit(closed):
    """``make_jaxpr`` of an already-jitted fn yields a one-equation
    wrapper — every invar feeds a single jit call — which defeats
    use/def analysis: every input looks consumed.  When the wrapper is
    exactly that trivial (one jit eqn fed the outer invars in order),
    hand rules the body jaxpr instead; positional labeling still holds
    because jit binds its operands 1:1."""
    jaxpr = closed.jaxpr
    if (
        len(jaxpr.eqns) == 1
        and jaxpr.eqns[0].primitive.name == "jit"
        and tuple(map(id, jaxpr.eqns[0].invars)) == tuple(map(id, jaxpr.invars))
    ):
        inner = jaxpr.eqns[0].params.get("jaxpr")
        if inner is not None and len(inner.jaxpr.invars) == len(jaxpr.invars):
            return inner
    return closed


@dataclasses.dataclass
class AuditProgram:
    """One traced entry point: the closed jaxpr, a label per flat input
    variable, and (lazily) the lowered StableHLO text."""

    name: str
    closed: Any
    invar_labels: tuple[str, ...]
    n_donated: int = 0
    _lower_thunk: Callable[[], str] | None = None
    _lowered_text: str | None = None
    _compile_thunk: Callable[[], str] | None = None
    _compiled_text: str | None = None
    _cost_profile: Any = None  # cost_rules.cost_profile caches here

    @classmethod
    def capture(
        cls,
        fn: Callable,
        *args,
        name: str = "program",
        donate_argnums: tuple[int, ...] = (),
    ) -> "AuditProgram":
        """Trace ``fn(*args)``; args may be arrays or ShapeDtypeStructs.

        ``donate_argnums`` drives the donation-coverage accounting AND the
        lowering: if ``fn`` is already jitted (has ``.lower``) its own
        donation settings are used, otherwise the capture jits it with
        exactly these argnums.
        """
        closed = _unwrap_trivial_jit(jax.make_jaxpr(fn)(*args))
        labels = _tree_labels(args)
        if len(labels) != len(closed.jaxpr.invars):
            # tracing didn't flatten 1:1 (static args, captured trees):
            # label-based rules will refuse rather than silently misbind
            labels = ()
        n_donated = sum(
            len(jax.tree_util.tree_leaves(args[i])) for i in donate_argnums
        )

        def jitted():
            return fn if hasattr(fn, "lower") else jax.jit(
                fn, donate_argnums=donate_argnums
            )

        def lower() -> str:
            return jitted().lower(*args).as_text()

        def compile_() -> str:
            # AOT: abstract args in, optimized per-device HLO text out —
            # compiles the executable without allocating any buffer
            return jitted().lower(*args).compile().as_text()

        return cls(
            name=name,
            closed=closed,
            invar_labels=labels,
            n_donated=n_donated,
            _lower_thunk=lower,
            _compile_thunk=compile_,
        )

    @property
    def lowered_text(self) -> str:
        if self._lowered_text is None:
            if self._lower_thunk is None:
                raise RuntimeError(
                    f"program {self.name!r} was built without a lowering"
                )
            self._lowered_text = self._lower_thunk()
        return self._lowered_text

    @property
    def compiled_text(self) -> str:
        """Optimized (post-fusion, SPMD-partitioned) HLO of the AOT-compiled
        entry point — the text the quantitative cost analysis walks."""
        if self._compiled_text is None:
            if self._compile_thunk is None:
                raise RuntimeError(
                    f"program {self.name!r} was built without a compilation"
                )
            self._compiled_text = self._compile_thunk()
        return self._compiled_text

    def labeled_invars(self) -> tuple[tuple[str, Any], ...]:
        """(label, invar) pairs; empty labels mean capture couldn't match
        flat inputs to tree paths (rules that need names must complain)."""
        if not self.invar_labels:
            return ()
        return tuple(zip(self.invar_labels, self.closed.jaxpr.invars))
