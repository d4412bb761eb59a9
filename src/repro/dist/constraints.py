"""Activation sharding constraints.

``constrain_activation`` is called on every residual-stream tensor and
batch input (see models/model.py, models/transformer.py, launch/steps.py).
Under an active mesh with installed ShardingRules it pins the activation
layout so GSPMD doesn't invent resharding chatter inside the layer stack:

  * batch axis  -> the data axis (always)
  * sequence    -> the model axis, but only for the scan *carry*
    (``carry=True``): the residual stream rides sequence-sharded between
    blocks and is gathered at block entry (sequence parallelism)

Outside a mesh (unit tests, CPU smoke runs) every call is an identity —
the constraint is advisory placement, never semantics.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.dist.sharding import P, ShardingRules

_RULES: Optional[ShardingRules] = None


def install(rules: ShardingRules) -> None:
    """Set process-global rules (the dry-run / launcher call this once)."""
    global _RULES
    _RULES = rules


def installed() -> Optional[ShardingRules]:
    return _RULES


def _active_mesh():
    try:
        from jax.interpreters import pxla

        mesh = pxla.thread_resources.env.physical_mesh
        if mesh is not None and not mesh.empty:
            return mesh
    except Exception:
        return None
    return None


def _in_manual_context() -> bool:
    """True inside a ``shard_map`` body (mesh axes are manual there —
    ``with_sharding_constraint`` over those axes is at best a no-op and
    at worst a hard error, so the constraint must stand down)."""
    try:
        from jax._src.core import get_axis_env

        return bool(get_axis_env().axis_sizes)
    except Exception:
        return False


def constrain_activation(x: jax.Array, *, carry: bool = False) -> jax.Array:
    """Pin an activation's sharding; identity when no mesh/rules are active.

    Only rank-2/3 float batch-major tensors are constrained — anything else
    (scalars, threshold vectors, integer token ids of other ranks) passes
    through untouched.  Inside a traced context with no rules installed,
    or inside a ``shard_map`` body (manual axes), this is an explicit
    no-op — model code must be callable under any tracer without the
    process-global install ever having happened.
    """
    rules = _RULES
    if rules is None or not hasattr(x, "ndim"):
        return x
    if _in_manual_context():
        return x
    mesh = _active_mesh()
    if mesh is None:
        return x
    if x.ndim == 2:  # (B, S) token ids
        spec = P(rules.act_batch, None)
    elif x.ndim == 3:  # (B, S, d) activations
        seq = rules.act_seq if carry else None
        spec = P(rules.act_batch, seq, None)
    else:
        return x
    if not _applies(spec, x.shape, mesh):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _applies(spec, shape, mesh) -> bool:
    """True when every axis ``spec`` names is in ``mesh`` and divides its
    dim.  The rules are written for the production meshes; a smaller mesh
    (no ``pod`` axis) or a batch below the data-axis size skips the
    constraint; any other failure to apply it raises."""
    for dim, entry in zip(shape, spec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            if a is None:
                continue
            if a not in mesh.shape:
                return False
            size *= mesh.shape[a]
        if dim % size:
            return False
    return True
