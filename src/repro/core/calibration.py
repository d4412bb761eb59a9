"""Calibration observers (paper §2, §4.1.2).

The paper calibrates activation thresholds on ~100 unlabeled images before
fine-tuning: "A set of data is provided to the network input to find desired
thresholds (in the example above — the maximum absolute value) of each
layer."  We implement the max-abs observer (paper default) plus a percentile
observer (a standard robustification against exactly the outlier problem the
paper's Figure 1 illustrates); both are functional — `update` returns a new
observer state so calibration can run under jit/pjit.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.quant import QuantSpec

ObserverKind = Literal["max_abs", "percentile", "min_max"]


def init_observer(spec: QuantSpec, channels: int | None = None,
                  lead_shape: tuple = ()) -> dict:
    """Fresh observer state.

    For per-channel (vector) mode pass the channel count.  ``lead_shape``
    prepends stacked-layer dims (scanned stacks slice observers per layer).
    State fields:
      t_max : running max |x|          (symmetric)
      t_min / t_hi : running min/max   (asymmetric)
      count : number of batches seen
    """
    shape = tuple(lead_shape) + (
        (channels,) if (spec.per_channel and channels) else ()
    )
    z = jnp.zeros(shape, jnp.float32)
    return {
        "t_max": z,
        "t_min": jnp.full(shape, jnp.inf, jnp.float32),
        "t_hi": jnp.full(shape, -jnp.inf, jnp.float32),
        "count": jnp.zeros(shape, jnp.int32),
    }


def _reduce_axes(x: jax.Array, spec: QuantSpec) -> tuple[int, ...]:
    if spec.per_channel:
        return tuple(i for i in range(x.ndim) if i != (spec.channel_axis % x.ndim))
    return tuple(range(x.ndim))


def update_observer(
    state: dict,
    x: jax.Array,
    spec: QuantSpec,
    kind: ObserverKind = "max_abs",
    percentile: float = 99.99,
) -> dict:
    """One calibration step: fold batch statistics into the observer."""
    axes = _reduce_axes(x, spec)
    xf = x.astype(jnp.float32)
    if kind == "percentile":
        # Robust threshold: high percentile of |x| instead of the raw max.
        # Tracks the *running mean* of per-batch percentiles, which converges
        # to a stable threshold even with heavy-tailed activations (Fig. 1).
        batch_t = jnp.percentile(jnp.abs(xf), percentile, axis=axes)
        c = state["count"].astype(jnp.float32)
        t_max = (state["t_max"] * c + batch_t) / (c + 1.0)
    else:
        batch_t = jnp.max(jnp.abs(xf), axis=axes)
        t_max = jnp.maximum(state["t_max"], batch_t)
    t_min = jnp.minimum(state["t_min"], jnp.min(xf, axis=axes))
    t_hi = jnp.maximum(state["t_hi"], jnp.max(xf, axis=axes))
    return {
        "t_max": t_max,
        "t_min": t_min,
        "t_hi": t_hi,
        "count": state["count"] + 1,
    }


def update_expert_observer(state: dict, x: jax.Array, row_expert: jax.Array,
                           row_valid: jax.Array, num_experts: int,
                           kind: ObserverKind = "max_abs") -> dict:
    """One calibration step of per-expert observers (state leaves (E,)):
    rows (M, in) grouped by expert, ``row_expert`` (M,) each row's expert,
    ``row_valid`` (M,) False on padding rows, which no observer sees."""
    if kind == "percentile":
        raise ValueError("per-expert observers keep running max/min only; "
                         "use observer='max_abs' for expert layers")
    xf = x.astype(jnp.float32)
    e = row_expert
    seen = jnp.zeros((num_experts,), bool).at[e].max(row_valid)
    amax = jnp.where(row_valid, jnp.max(jnp.abs(xf), axis=-1), 0.0)
    lo = jnp.where(row_valid, jnp.min(xf, axis=-1), jnp.inf)
    hi = jnp.where(row_valid, jnp.max(xf, axis=-1), -jnp.inf)
    z = jnp.zeros((num_experts,), jnp.float32)
    return {
        "t_max": jnp.maximum(state["t_max"], z.at[e].max(amax)),
        "t_min": jnp.minimum(state["t_min"], (z + jnp.inf).at[e].min(lo)),
        "t_hi": jnp.maximum(state["t_hi"], (z - jnp.inf).at[e].max(hi)),
        "count": state["count"] + seen.astype(jnp.int32),
    }


def observer_thresholds(state: dict, spec: QuantSpec) -> dict:
    """Finalize calibration into threshold parameters (§3.1.3 init).

    Symmetric: T_max from the observer, trained scale alpha=1.
    Asymmetric: (T_l, T_r) from min/max, alpha_t=0, alpha_r=1 (§3.1.4).
    An entry the calibration never saw (an expert no calibration token
    was routed to) takes the largest threshold along its last axis.
    """
    shape = state["t_max"].shape
    ones = jnp.ones(shape, jnp.float32)
    if len(shape):
        seen = state["count"] > 0
        state = dict(state, t_max=jnp.where(seen, state["t_max"], jnp.max(
            jnp.where(seen, state["t_max"], 0.0), axis=-1, keepdims=True)))
    t_min = jnp.where(jnp.isfinite(state["t_min"]), state["t_min"], 0.0)
    t_hi = jnp.where(jnp.isfinite(state["t_hi"]), state["t_hi"], 0.0)
    return {
        "t_max": jnp.maximum(state["t_max"], 1e-8),
        "t_l": t_min,
        "t_r": jnp.maximum(t_hi, t_min + 1e-8),
        "alpha": ones,
        "alpha_t": jnp.zeros(shape, jnp.float32),
        "alpha_r": ones,
    }
