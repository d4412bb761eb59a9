"""Shared jaxpr plumbing for the static analyzers.

Every checker in this package walks traced jaxprs of the serving entry
points (repro.analysis.entrypoints).  This module owns the one recursive
walk — into ``scan``/``while``/``cond``/``pjit``/``custom_vjp`` bodies
and into ``pallas_call`` kernel jaxprs — so each analyzer is a flat pass
over equations, and all of them agree on what "the whole graph" means.

Source attribution is best-effort: JAX equations carry a traceback; we
surface the innermost repo frame as ``file:line`` and the full frame
function-name list for allowlist scoping (an
:class:`repro.analysis.dtype_drift.AllowRule` can match "somewhere under
``compressed_psum``" without hardcoding line numbers).
"""
from __future__ import annotations

from typing import Iterator

from jax.extend import core as jcore


def subjaxprs(eqn) -> list:
    """All jaxprs nested in an equation's params (scan/while/cond bodies,
    pjit calls, custom_vjp branches, pallas kernel bodies, ...)."""
    out = []
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else [val]):
            if isinstance(item, jcore.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jcore.Jaxpr):
                out.append(item)
            elif hasattr(item, "jaxpr") and isinstance(
                    getattr(item, "jaxpr", None), jcore.Jaxpr):
                out.append(item.jaxpr)
    return out


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first walk over every equation, recursing into subjaxprs.
    Accepts a Jaxpr or ClosedJaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub)


def find_eqns(jaxpr, primitive_name: str) -> list:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == primitive_name]


def _frames(eqn):
    tb = getattr(getattr(eqn, "source_info", None), "traceback", None)
    if tb is None:
        return []
    try:
        return list(tb.frames)
    except Exception:
        return []


def eqn_location(eqn) -> str:
    """Innermost repo frame as ``file:line`` (falls back to the innermost
    frame of any kind, or "")."""
    frames = _frames(eqn)
    best = ""
    for fr in frames:
        loc = f"{fr.file_name}:{fr.line_num}"
        if "/repro/" in fr.file_name or "/tests/" in fr.file_name:
            return loc
        if not best:
            best = loc
    return best


def eqn_function_names(eqn) -> list[str]:
    """Function names on the equation's traceback, innermost first —
    the scoping key for declarative allowlists."""
    return [fr.function_name for fr in _frames(eqn)]


def var_dtype(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def var_shape(v):
    return getattr(getattr(v, "aval", None), "shape", None)


def producer_map(jaxpr) -> dict:
    """var -> producing eqn, one flat (non-recursive) map per jaxpr
    block.  Backward walks (quantize-ancestry) stay within a block:
    values entering a block are parameters/consts there, which is exactly
    the conservative boundary we want."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def ancestor_prims(var, producers: dict, *, max_depth: int = 12) -> set:
    """Primitive names reachable backward from ``var`` within one jaxpr
    block, up to ``max_depth`` producer hops.  Call-like producers
    (jnp.round/jnp.clip arrive as one-eqn ``pjit[name=round]`` wrappers)
    contribute the primitives INSIDE their subjaxprs too, so a quantizer
    reads as {"pjit", "round", "max", "min", ...} rather than opaque
    "pjit" hops."""
    seen_prims: set = set()
    frontier = [var]
    seen_vars = set()
    for _ in range(max_depth):
        nxt = []
        for v in frontier:
            eqn = producers.get(v)
            if eqn is None:
                continue
            seen_prims.add(eqn.primitive.name)
            for sub in subjaxprs(eqn):
                seen_prims.update(e.primitive.name for e in iter_eqns(sub))
            for iv in eqn.invars:
                if not isinstance(iv, jcore.Var) or iv in seen_vars:
                    continue
                seen_vars.add(iv)
                nxt.append(iv)
        if not nxt:
            break
        frontier = nxt
    return seen_prims


def blocks(jaxpr) -> Iterator:
    """Every jaxpr block in the graph (the top jaxpr plus each subjaxpr),
    for analyses that need per-block producer maps."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in subjaxprs(eqn):
            yield from blocks(sub)
