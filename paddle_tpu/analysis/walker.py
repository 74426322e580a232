"""Shared jaxpr traversal — the ONE walk used by every analysis rule
and by fluid.contrib.op_frequence.

A jaxpr is the unit XLA actually compiles, so walking it (instead of
Python source) sees exactly what will run on the chip: casts the
tracer inserted, constants it baked in, callbacks that punch through
to the host, and the sub-jaxprs of scan/cond/while/pjit/custom-vjp
bodies.  ``walk`` yields ``(parent_jaxpr, eqn)`` depth-first so
callers can both count ops globally and reason per-nesting-level
(op_frequence's adjacent-pair statistic pairs only within one level).

Nothing here executes device code: ``trace_jaxpr`` is jax.make_jaxpr
(abstract evaluation), usable with concrete arrays *or*
jax.ShapeDtypeStruct placeholders.
"""
import math

import numpy as np

import jax
from jax._src import source_info_util
from jax.extend import core as _core

__all__ = ['trace_jaxpr', 'walk', 'subjaxprs', 'eqn_location',
           'aval_bytes', 'is_literal', 'const_derived_vars']

Literal = _core.Literal


def trace_jaxpr(fn, *example_args, **example_kwargs):
    """Abstractly trace `fn` into a ClosedJaxpr (no device execution).

    `example_args` may be concrete arrays, pytrees of arrays, or
    jax.ShapeDtypeStruct placeholders."""
    return jax.make_jaxpr(fn)(*example_args, **example_kwargs)


def _as_jaxprs(v):
    if isinstance(v, _core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, _core.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for item in v for j in _as_jaxprs(item)]
    return []


def subjaxprs(eqn):
    """Sub-jaxprs carried in an equation's params (scan/cond/while/pjit
    bodies, custom_vjp calls, ...) — including ones nested in tuples
    (cond branches)."""
    for v in eqn.params.values():
        for j in _as_jaxprs(v):
            yield j


def walk(jaxpr):
    """Depth-first (parent_jaxpr, eqn) over `jaxpr` and every
    sub-jaxpr.  The parent identifies the nesting level an equation
    lives in (adjacency is only meaningful within one level)."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in subjaxprs(eqn):
            yield from walk(sub)


def is_literal(v):
    return isinstance(v, Literal)


def eqn_location(eqn):
    """(file, line) of the user frame that emitted this equation, or
    (None, None) when the equation carries no user frame.  Uses jax's
    own user-frame filter so jax-internal frames are skipped."""
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None, None
    return fr.file_name, fr.start_line


def aval_bytes(aval):
    """Byte size of an abstract value (0 when it has no shape/dtype)."""
    shape = getattr(aval, 'shape', None)
    dtype = getattr(aval, 'dtype', None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:      # symbolic dim (jax.export) — unknown size
            return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtype (PRNG key avals: 'key<fry>') — numpy cannot
        # size it; its base uint32 payload is what HBM actually holds
        base = getattr(getattr(dtype, '_impl', None), 'key_shape', None)
        itemsize = 4 * math.prod(base) if base else 4
    return n * itemsize


def const_derived_vars(jaxpr):
    """Dataflow: the set of Vars in `jaxpr` (this level only) whose
    value depends ONLY on constants/literals — i.e. on nothing fed
    through the jaxpr's invars.  These are materialized identically on
    every device (XLA replicates constants), which is what the
    replicated-giant rule keys on."""
    derived = set(jaxpr.constvars)
    for eqn in jaxpr.eqns:
        ins = [v for v in eqn.invars if not is_literal(v)]
        if all(v in derived for v in ins):
            derived.update(eqn.outvars)
    return derived
