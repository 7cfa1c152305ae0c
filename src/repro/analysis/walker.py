"""The single recursive jaxpr walker every analysis rule shares.

Every program-level invariant this repo has earned (one pallas launch per
step, zero pointer gathers on device, donated state, no host callbacks)
is a statement about the *traced program*, and every one of them needs
the same traversal: visit each equation of a (closed) jaxpr, then recurse
into every sub-jaxpr hiding in equation params — jit bodies, scan/while
bodies, cond branches, custom_vjp call jaxprs, shard_map bodies, pallas
kernel bodies.  Rules must never hand-roll that recursion (the pre-PR-6
copies in tests drifted exactly this way); they consume ``walk`` /
``count_primitive`` / ``used_var_ids`` and stay one-liners.

Traversal contract (DESIGN.md §7): sub-jaxprs are discovered by duck
typing on equation param values — anything with ``.eqns`` is a jaxpr,
anything with ``.jaxpr`` is a closed jaxpr, and lists/tuples are searched
elementwise.  That keeps the walker robust across jax API drift (the set
of higher-order primitives and their param names change; the two shapes
of "a jaxpr value" do not).  Thunks and callables in params (e.g.
``custom_vjp``'s ``fwd_jaxpr_thunk``) are deliberately NOT forced: the
walker only audits program structure that already exists.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator


def as_jaxpr(jaxpr_like):
    """Accept a ``ClosedJaxpr``, a raw ``Jaxpr``, or anything wrapping one
    (e.g. the object ``jax.make_jaxpr`` returns) and hand back the raw
    jaxpr the walker iterates."""
    inner = getattr(jaxpr_like, "jaxpr", None)
    if inner is not None:
        return inner
    if hasattr(jaxpr_like, "eqns"):
        return jaxpr_like
    raise TypeError(f"not a jaxpr: {type(jaxpr_like).__name__}")


def sub_jaxprs(value) -> Iterator[Any]:
    """Yield every raw jaxpr contained in one equation-param value."""
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from sub_jaxprs(item)


def closed_sub_jaxprs(value) -> Iterator[Any]:
    """Like ``sub_jaxprs`` but yields only CLOSED jaxprs (the ones that
    carry ``.consts``) — the traversal ``ConstantCapture`` needs."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from closed_sub_jaxprs(item)


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One visited equation plus the path of enclosing equations, e.g.
    ``eqns[3]:scan/eqns[0]:pallas_call`` — stable enough to point a human
    at the offending sub-program."""

    eqn: Any
    path: str

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def walk(jaxpr_like, _path: str = "") -> Iterator[EqnSite]:
    """Depth-first over every equation of ``jaxpr_like`` and all its
    sub-jaxprs.  The yielded path names each enclosing equation by index
    and primitive."""
    jaxpr = as_jaxpr(jaxpr_like)
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{_path}eqns[{i}]:{eqn.primitive.name}"
        yield EqnSite(eqn, here)
        for key, value in eqn.params.items():
            for sub in sub_jaxprs(value):
                yield from walk(sub, _path=f"{here}.{key}/")


def count_primitive(jaxpr_like, name: str) -> int:
    """Recursive count of equations binding primitive ``name`` (e.g.
    ``pallas_call`` — the heavy launch count the fusion work optimizes)."""
    return sum(1 for site in walk(jaxpr_like) if site.primitive == name)


def primitive_counts(jaxpr_like) -> dict[str, int]:
    """Histogram of every primitive in the program — the report's
    at-a-glance program shape."""
    counts: dict[str, int] = {}
    for site in walk(jaxpr_like):
        counts[site.primitive] = counts.get(site.primitive, 0) + 1
    return counts


def used_var_ids(jaxpr_like, *, include_outputs: bool = True) -> set[int]:
    """``id()`` of every variable consumed by any equation (recursively)
    or returned as an output.  Sub-jaxprs bind fresh variable objects, so
    membership tests against the TOP-LEVEL invars are exact: a top-level
    invar is "used" iff its id lands in this set."""
    jaxpr = as_jaxpr(jaxpr_like)
    used: set[int] = set()
    if include_outputs:
        used.update(map(id, jaxpr.outvars))
    for site in walk(jaxpr):
        used.update(map(id, site.eqn.invars))
    return used


def iter_consts(closed) -> Iterator[tuple[str, Any]]:
    """Yield ``(path, const)`` for every constant baked into the closed
    jaxpr — top level first, then constants of closed sub-jaxprs (a
    sub-program can capture its own)."""
    for const in getattr(closed, "consts", ()):
        yield "consts", const
    for site in walk(closed):
        for key, value in site.eqn.params.items():
            for sub in closed_sub_jaxprs(value):
                for const in sub.consts:
                    yield f"{site.path}.{key}", const
