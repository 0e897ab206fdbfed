"""Jaxpr introspection helpers for lowering pins.

The batch-native engine's structural claim — a constant number of
``pallas_call`` eqns per serve step with no batch-sized grid dimension —
is asserted both by tests (tests/test_batchfuse.py) and by the CI-gated
``batchfuse`` benchmark verdict.  ONE copy of the jaxpr walker lives here
so a future JAX upgrade that moves ``grid_mapping`` breaks both consumers
the same way instead of letting them disagree about the same lowering.

The early-stop engines' claim — no reduction over a full count buffer
inside the walk's while body — is pinned with the equation walker below
(``iter_eqns`` / ``full_buffer_reduces``), shared by the dense, wide-lane
and sharded engine tests.
"""

from __future__ import annotations

from typing import List, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr


def pallas_grids(jaxpr) -> List[Tuple[int, ...]]:
    """Every ``pallas_call`` grid in a ClosedJaxpr, nested jaxprs included.

    Returns the grids in eqn order (while/cond/scan bodies walked
    recursively), each as a tuple of ints.
    """
    grids: List[Tuple[int, ...]] = []

    def rec(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(
                    tuple(int(d) for d in eqn.params["grid_mapping"].grid)
                )
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    rec(v.jaxpr)
                elif isinstance(v, (list, tuple)):
                    for x in v:
                        if hasattr(x, "jaxpr"):
                            rec(x.jaxpr)

    rec(jaxpr.jaxpr)
    return grids


# ---------------------------------------------------------------------------
# the structural claim: no full-buffer reduction inside the while body
# ---------------------------------------------------------------------------

_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "argmax", "argmin",
}


def _sub_jaxprs(val):
    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr):
    """All equations, recursing into sub-jaxprs but not into pallas_call
    (kernel-internal tile math is VMEM-resident, not a buffer reduction)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if "pallas" in eqn.primitive.name:
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def full_buffer_reduces(jaxpr, min_size):
    """``(primitive, shape)`` of every reduction over an operand of at
    least ``min_size`` elements — the pre-fusion early-stop pattern (a
    full count-buffer recount per chunk)."""
    found = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in _REDUCE_PRIMS:
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "size", 0) >= min_size:
                    found.append((eqn.primitive.name, tuple(aval.shape)))
    return found
