"""Exhaustive jaxpr traversal.

The repo's original walker lived inside
``tests/test_download_accounting.py`` and only descended into sub-jaxprs
it happened to find by scanning ``eqn.params`` for ``Jaxpr`` /
``ClosedJaxpr`` values in lists and tuples.  That covers ``scan`` and
``pjit`` but is blind to the call-like primitives whose bodies hide
behind other param names or wrapper objects — most importantly
``custom_vjp_call`` (param ``call_jaxpr``) and ``remat2`` (an *open*
``Jaxpr`` under param ``jaxpr``), which is exactly where the flash
attention kernels of PR 3 live.

This module walks everything: every eqn of the top-level jaxpr and,
recursively, every eqn of every sub-jaxpr reachable through any param,
including

- ``scan`` / ``while`` / ``cond``            (ClosedJaxpr params, lists)
- ``pjit`` / ``xla_call`` / ``core_call``    (ClosedJaxpr ``jaxpr``)
- ``custom_vjp_call`` / ``custom_jvp_call`` (``call_jaxpr``;
  the fwd/bwd thunks are Python callables, not jaxprs, and are *not*
  invoked — tracing arbitrary user thunks from an auditor is fragile.
  The bwd body is auditable by tracing ``jax.grad`` of the target, which
  inlines it)
- ``remat2`` / ``checkpoint``                (open ``Jaxpr`` param)
- ``pallas_call``                            (kernel ``jaxpr`` param)

Every visited eqn is yielded together with its *path* — a ``/``-joined
string of enclosing primitive names like ``"scan/pjit/remat2"`` — so
rules can scope themselves (e.g. the dtype rule only fires inside
regions the caller declared bf16) and reports can say *where* a
violation lives, and the walk records the set of descended-into
primitives so tests can assert coverage (``custom_vjp`` and ``remat``
descent is an acceptance criterion of the analysis subsystem).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Iterator

from jax._src import core as jax_core

Jaxpr = jax_core.Jaxpr
ClosedJaxpr = jax_core.ClosedJaxpr


@dataclass(frozen=True)
class EqnSite:
    """One equation, with enough context for a rule to judge it."""

    eqn: Any                  # jax.core.JaxprEqn
    path: str                 # "" at top level, else "scan/pjit/..."
    depth: int                # number of enclosing sub-jaxprs

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


@dataclass
class WalkStats:
    """What a walk actually covered — asserted on by the test suite."""

    eqn_count: int = 0
    max_depth: int = 0
    descended_into: set = field(default_factory=set)  # primitive names

    def visited(self, primitive: str) -> bool:
        return primitive in self.descended_into


def _sub_jaxprs(params: dict) -> Iterator[Jaxpr]:
    """Yield every Jaxpr reachable from an eqn's params.

    Generic over param names: any ``Jaxpr``/``ClosedJaxpr`` value, or one
    nested inside a list/tuple, is a sub-jaxpr.  This single rule covers
    scan (``jaxpr``: ClosedJaxpr), cond (``branches``: tuple of
    ClosedJaxpr), while (``cond_jaxpr``/``body_jaxpr``), pjit
    (``jaxpr``), custom_vjp/custom_jvp (``fun_jaxpr``/``call_jaxpr``),
    remat2 (``jaxpr``: open Jaxpr) and pallas_call (``jaxpr``) without a
    per-primitive table that would rot as JAX renames params.
    """
    for val in params.values():
        if isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for item in val:
                if isinstance(item, ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, Jaxpr):
                    yield item


def iter_eqns(jaxpr, stats: WalkStats | None = None) -> Iterator[EqnSite]:
    """Depth-first walk over every eqn of ``jaxpr`` and all sub-jaxprs.

    ``jaxpr`` may be a ``Jaxpr``, a ``ClosedJaxpr``, or the object
    returned by ``jax.make_jaxpr(fn)(*args)``.  If ``stats`` is given it
    is filled in as a side effect.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    if stats is None:
        stats = WalkStats()

    def _walk(jxp: Jaxpr, path: str, depth: int) -> Iterator[EqnSite]:
        stats.max_depth = max(stats.max_depth, depth)
        for eqn in jxp.eqns:
            stats.eqn_count += 1
            yield EqnSite(eqn=eqn, path=path, depth=depth)
            sub = list(_sub_jaxprs(eqn.params))
            if sub:
                stats.descended_into.add(eqn.primitive.name)
                child_path = (path + "/" if path else "") + eqn.primitive.name
                for s in sub:
                    yield from _walk(s, child_path, depth + 1)

    yield from _walk(jaxpr, "", 0)


def walk(jaxpr) -> tuple[list[EqnSite], WalkStats]:
    """Eager variant of :func:`iter_eqns`: (all sites, coverage stats)."""
    stats = WalkStats()
    sites = list(iter_eqns(jaxpr, stats))
    return sites, stats


def collect_shapes(jaxpr) -> set:
    """Every intermediate/output shape appearing anywhere in the jaxpr.

    This is the behaviour of the original test-local walker (which
    recorded ``outvar.aval.shape`` per eqn), preserved as a convenience
    so the download-accounting test keeps its assertions bit-identical
    in intent while gaining custom_vjp/remat descent.
    """
    shapes = set()
    for site in iter_eqns(jaxpr):
        for v in site.eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                shapes.add(tuple(aval.shape))
    return shapes
