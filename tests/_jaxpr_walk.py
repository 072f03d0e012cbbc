"""Walk a traced program's jaxprs, nested ones included.

The memory-honesty tests trace a program with ``jax.make_jaxpr`` and bound
the largest intermediate it creates, including inside ``lax.map`` /
``while`` bodies and Pallas kernel bodies, which live in equation params.
"""

from jax.extend.core import ClosedJaxpr, Jaxpr


def sub_jaxprs(eqn):
    """Jaxprs held in one equation's params (loop bodies, branches,
    Pallas kernel bodies)."""
    for val in eqn.params.values():
        for x in val if isinstance(val, (tuple, list)) else (val,):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def max_outvar_elems(jaxpr) -> int:
    """Element count of the largest array any (nested) equation outputs."""
    seen = 0
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                elems = 1
                for s in shape:
                    elems *= int(s)
                seen = max(seen, elems)
    return seen


def pallas_bodies(jaxpr) -> list:
    """The kernel-body jaxprs of every ``pallas_call`` in ``jaxpr``."""
    return [sub for eqn in iter_eqns(jaxpr)
            if "pallas" in eqn.primitive.name for sub in sub_jaxprs(eqn)]
