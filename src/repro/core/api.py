"""FAT quantization context — the integration point between the paper's
technique (repro.core.quant) and the model substrate (repro.models).

Modes
-----
  none       full-precision forward (the distillation *teacher*, §3.2)
  calibrate  full-precision forward that also feeds activation observers
             (paper §2 "calibration procedure"; 100 unlabeled samples)
  fake       fake-quantized forward with trained threshold scales — the
             distillation *student* (§3.1.3-3.1.5); differentiable via STE
  int8       real integer serving path: int8 weights resident in memory,
             int8 activations with static calibrated thresholds, int32
             accumulation (§2, eq. 20), dequant fused into the epilogue

State layout
------------
``qparams``   flat dict  path -> {"act": {...}, "w": {...}} of threshold
              states.  Trainable leaves are exactly the alpha scales
              (alpha / alpha_t / alpha_r) and optional pointwise scales —
              everything else (t_max, t_l, t_r, observers) is frozen
              calibration data.  This is what makes FAT *fast*: the
              optimizer state is a few scalars per layer.
``params``    the model pytree; in int8 mode quantized Dense leaves hold
              {"w_q": int8, "w_scale": f32[C]} instead of {"w": bf16}.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import calibration as calib
from repro.core import quant as Q

Mode = str  # 'none' | 'calibrate' | 'fake' | 'int8'


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Which FAT variant to run (the paper's experiment grid, Tables 1-2)."""

    bits: int = 8
    act_symmetric: bool = True          # Table 1/2: symmetric vs asymmetric
    weight_per_channel: bool = True     # §3.1.5: vector vs scalar mode
    act_per_channel: bool = False       # activations stay per-tensor (paper)
    pointwise_scales: bool = False      # §4.2 trainable [0.75, 1.25] scales
    observer: str = "max_abs"           # 'max_abs' (paper) | 'percentile'
    percentile: float = 99.99
    skip_patterns: tuple[str, ...] = () # layer paths excluded (e.g. routers)
    use_pallas: bool = False            # Pallas kernels on real TPU hot path
    kv_int8: bool = False               # quantized KV cache (per-head static T)
    kv_bits: int = 8                    # KV cache width: 8, or 4 (packed nibbles)

    @functools.cached_property
    def _skip_res(self) -> tuple[re.Pattern, ...]:
        # compiled once per policy instance (cached_property writes the
        # instance __dict__ directly, which frozen= does not block);
        # skips() runs per layer per call, so re-running re.compile via
        # re.search's internal cache lookup was measurable overhead
        return tuple(re.compile(p) for p in self.skip_patterns)

    def skips(self, path: str) -> bool:
        return any(p.search(path) for p in self._skip_res)

    def weight_spec(self, channel_axis: int = -1) -> Q.QuantSpec:
        return Q.QuantSpec(
            bits=self.bits,
            symmetric=True,  # weights are symmetric in the paper (eq. 1-4)
            per_channel=self.weight_per_channel,
            channel_axis=channel_axis,
        )

    def act_spec(self, unsigned: bool = False) -> Q.QuantSpec:
        return Q.QuantSpec(
            bits=self.bits,
            symmetric=self.act_symmetric,
            unsigned=unsigned,
            per_channel=self.act_per_channel,
        )

    def kv_spec(self) -> Q.QuantSpec:
        """K/V cache entries (B, S, KV, D): symmetric int8/int4 with one
        static threshold per KV head (channel_axis=-2).  Per-head rather
        than per-tensor because K magnitudes vary strongly across heads
        (rope frequencies), and per-head scales stay O(KV) resident
        floats.  ``kv_bits`` picks the integer width (levels = 127 or
        7); the cache stores int4 as packed nibbles."""
        return Q.QuantSpec(
            bits=self.kv_bits,
            symmetric=True,
            per_channel=True,
            channel_axis=-2,
        )


@dataclasses.dataclass
class QuantCtx:
    """Threaded through every forward; mutable only during tracing.

    ``updates`` collects observer states during a 'calibrate' trace; the
    step function merges them back into qparams functionally, so
    calibration jits/pjits cleanly.
    """

    mode: Mode
    policy: QuantPolicy
    qparams: dict
    updates: dict = dataclasses.field(default_factory=dict)

    def enabled(self, layer) -> bool:
        return (
            self.mode != "none"
            and getattr(layer, "quantize", False)
            and not self.policy.skips(layer.path)
        )


def make_ctx(mode: Mode, policy: QuantPolicy, qparams: dict | None = None) -> QuantCtx:
    return QuantCtx(mode=mode, policy=policy, qparams=qparams or {})


# ---------------------------------------------------------------------------
# qparams construction
# ---------------------------------------------------------------------------


def init_qparams(model, params: dict, policy: QuantPolicy) -> dict:
    """Build threshold state for every quantizable layer.

    Weight thresholds come straight from the weights (T_w = max|W|, eq. 2);
    activation thresholds start as empty observers to be filled by
    calibration.  Weight alpha starts at 1.0 (threshold == T_max, §3.1.3).
    """
    from repro.models.module import ExpertDense

    qparams: dict = {}
    for layer, lp in _quant_layers_with_params(model, params, policy):
        w = lp["w"]
        wspec = policy.weight_spec()
        # scanned stacks store weights with a leading (L,) axis; thresholds
        # get the same leading axis so lax.scan slices them per layer
        base_ndim = 3 if isinstance(layer, ExpertDense) else 2
        lead = w.shape[: w.ndim - base_ndim]
        # |w| and its max are exact in the weights' own dtype: no float32
        # copy of the weights
        if wspec.per_channel:
            # per output channel: (out,) / (E, out), + leading stack dims
            t_w = jnp.max(jnp.abs(w), axis=-2).astype(jnp.float32)
        else:
            axes = tuple(range(w.ndim - base_ndim, w.ndim))
            t_w = jnp.max(jnp.abs(w), axis=axes).astype(jnp.float32)
        # expert layers observe one activation threshold per expert
        act_lead = lead + ((layer.num_experts,)
                           if isinstance(layer, ExpertDense) else ())
        entry = {
            "w": {
                "t_max": t_w,
                "alpha": jnp.ones_like(t_w),
            },
            "act": calib.init_observer(policy.act_spec(), lead_shape=act_lead),
        }
        if policy.pointwise_scales:
            entry["w"]["pointwise"] = jnp.ones(w.shape, jnp.float32)
        qparams[layer.path] = entry
    if policy.kv_int8:
        for attn, lp in _kv_attention_with_params(model, params):
            # scanned stacks carry (L,) on every weight; observers get the
            # same leading axis so lax.scan slices them per layer
            lead = lp["wk"]["w"].shape[:-2]
            spec = policy.kv_spec()
            qparams[kv_path(attn.path)] = {
                "k": calib.init_observer(spec, channels=attn.n_kv,
                                         lead_shape=lead),
                "v": calib.init_observer(spec, channels=attn.n_kv,
                                         lead_shape=lead),
            }
    return qparams


def kv_path(attn_path: str) -> str:
    """qparams key holding the KV-cache thresholds of one attention layer."""
    return f"{attn_path}/kv"


def is_kv_path(path: str) -> bool:
    """True for KV-cache threshold entries (structure {'k':…, 'v':…})."""
    return path.endswith("/kv")


def _kv_attention_with_params(model, params):
    """(decode-caching self-attention layer, its params subtree) pairs.

    Cross-attention caches encoder memory (computed once per request, not
    the decode bandwidth bottleneck) and bidirectional encoder layers
    never decode — neither owns a KV cache, so neither gets threshold
    state (observers on them would burn calibration compute for nothing).
    """
    from repro.models.attention import Attention  # avoid cycle

    for module, sub in model.walk_with_params(params):
        if isinstance(module, Attention) and not module.cross and module.causal:
            yield module, sub


def _quant_layers_with_params(model, params, policy: QuantPolicy | None = None):
    """(quantizable leaf layer, its params subtree) pairs, skip-filtered.

    Walks the module tree in parallel with the param pytree so the stable
    layer *path* (quantization-state key) pairs with the structural param
    location — the two need not share naming.
    """
    from repro.models.module import Dense, ExpertDense  # avoid cycle

    for module, sub in model.walk_with_params(params):
        if isinstance(module, (Dense, ExpertDense)) and module.quantize:
            if policy is not None and policy.skips(module.path):
                continue
            yield module, sub


def finalize_calibration(qparams: dict, policy: QuantPolicy, *,
                         train_thresholds: bool = False) -> dict:
    """Convert observer stats into threshold params (paper §3.1.3 init).

    KV-cache entries normally freeze to bare per-head thresholds — unlike
    activation thresholds they carry no trainable alpha: the cache is
    written and read with the same scale, so the FAT fine-tuning
    objective has no gradient signal through it (§2: everything static at
    serving time).  With ``train_thresholds=True`` each KV entry instead
    gains a trainable log2-domain threshold (``log2_t``, TQT-style —
    initialized at the §2 max-abs value), the fake-mode forward
    fake-quantizes K/V through it so the distillation loss reaches it,
    and ``freeze_thresholds`` collapses it back to a bare ``t_max`` for
    serving once fine-tuning is done.
    """
    out = {}
    for path, entry in qparams.items():
        if is_kv_path(path):  # KV observer entry: {"k": obs, "v": obs}
            # where(), not maximum(): a NaN-poisoned observer (e.g. a
            # non-finite calibration batch) must still floor — maximum
            # propagates the NaN straight into every cache scale
            kv = {
                kk: {"t_max": jnp.where(obs["t_max"] > 1e-8,
                                        obs["t_max"], 1e-8)}
                for kk, obs in entry.items()
            }
            if train_thresholds:
                for st in kv.values():
                    st["log2_t"] = jnp.log2(st["t_max"]).astype(jnp.float32)
            out[path] = kv
            continue
        e = dict(entry)
        e["act"] = calib.observer_thresholds(entry["act"], policy.act_spec())
        out[path] = e
    return out


def freeze_thresholds(qparams: dict) -> dict:
    """Collapse trained KV thresholds back to the frozen serving form.

    Every KV entry carrying a trained ``log2_t`` becomes a bare
    ``{"t_max": 2**log2_t}`` (floored like finalize_calibration), which
    is exactly what the serving path (`Attention._kv_scales`) reads —
    after this the engine cannot tell trained thresholds from §2 ones.
    """
    out = {}
    for path, entry in qparams.items():
        if is_kv_path(path) and any("log2_t" in st for st in entry.values()):
            out[path] = {
                kk: {"t_max": jnp.where(jnp.exp2(st["log2_t"]) > 1e-8,
                                        jnp.exp2(st["log2_t"]), 1e-8)}
                for kk, st in entry.items()
            }
        else:
            out[path] = entry
    return out


def trainable_mask(qparams: dict) -> dict:
    """Pytree of bools: True only on the trained FAT parameters —
    threshold scale factors, trained log2 thresholds (TQT mode), and
    pointwise scales if enabled."""
    trainable_keys = {"alpha", "alpha_t", "alpha_r", "pointwise", "log2_t"}

    def mask_entry(d):
        return {
            k: (mask_entry(v) if isinstance(v, dict) else k in trainable_keys)
            for k, v in d.items()
        }

    return {p: mask_entry(e) for p, e in qparams.items()}


def _flatten(params: dict, prefix: str = "") -> dict:
    flat = {}
    for k, v in params.items():
        kk = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(_flatten(v, kk))
        else:
            flat[kk] = v
    return flat


# ---------------------------------------------------------------------------
# Forward implementations (called by Dense / ExpertDense)
# ---------------------------------------------------------------------------


def _fq_act(x, astate, spec: Q.QuantSpec):
    if spec.symmetric:
        return Q.fake_quant_symmetric_fused(
            x, astate["t_max"], astate["alpha"], spec
        )
    return Q.fake_quant_asymmetric(
        x, astate["t_l"], astate["t_r"], astate["alpha_t"],
        astate["alpha_r"], spec,
    )


def _weight_threshold_shape(w: jax.Array) -> tuple[int, ...]:
    """Broadcast shape of per-out-channel thresholds against w.

    Dense w: (in, out) -> (1, out).  ExpertDense w: (E, in, out) ->
    (E, 1, out) — per-(expert, filter) thresholds, the vector mode of
    §3.1.5 generalized to batched expert weights.  Scanned stacks prepend
    (L,) to either form; the rule is uniform: every axis except the
    contraction (-2) keeps its own threshold.
    """
    return tuple(w.shape[:-2]) + (1, w.shape[-1])


def _fq_weight(w, wstate, spec: Q.QuantSpec):
    if "pointwise" in wstate:
        w = Q.apply_pointwise_scale(w, wstate["pointwise"].astype(w.dtype))
    if spec.per_channel:
        shape = _weight_threshold_shape(w)
        t = wstate["t_max"].reshape(shape)
        alpha = wstate["alpha"].reshape(shape)
        t_adj = jnp.maximum(Q.adjusted_threshold(t, alpha, spec), 1e-8)
        s = (spec.levels / t_adj).astype(jnp.float32)
        wq = Q.clip_grad_passthrough(
            Q.ste_round(w.astype(jnp.float32) * s), spec.qmin, spec.qmax
        )
        return (wq / s).astype(w.dtype)
    return Q.fake_quant_symmetric(
        w.astype(jnp.float32), wstate["t_max"], wstate["alpha"], spec
    ).astype(w.dtype)


def dense_forward(layer, params: dict, x: jax.Array, ctx: Optional[QuantCtx]):
    """All four modes for a Dense layer."""
    b = params.get("b")
    if _tp_reduce_axis(layer) is not None and (
            ctx is None or ctx.mode != "int8" or not ctx.enabled(layer)):
        # the float paths have no integer accumulator to reduce exactly —
        # a silent skip here would return per-shard partial products
        raise ValueError(
            f"{layer.path}: tensor-parallel serving requires mode='int8' "
            "with this layer quantized (the row epilogue reduces int32 "
            "accumulators; see repro.shard)")
    if ctx is None or not ctx.enabled(layer):
        y = x @ params["w"]
    elif ctx.mode == "calibrate":
        ctx.updates[layer.path] = calib.update_observer(
            ctx.qparams[layer.path]["act"],
            x,
            ctx.policy.act_spec(layer.act_unsigned),
            kind=ctx.policy.observer,
            percentile=ctx.policy.percentile,
        )
        y = x @ params["w"]
    elif ctx.mode == "fake":
        qs = ctx.qparams[layer.path]
        xq = _fq_act(x, qs["act"], ctx.policy.act_spec(layer.act_unsigned)).astype(
            x.dtype
        )
        wq = _fq_weight(params["w"], qs["w"], ctx.policy.weight_spec())
        y = xq @ wq
    elif ctx.mode == "int8":
        y = _int8_matmul(
            x,
            params["w_q"],
            params["w_scale"],
            ctx.qparams[layer.path]["act"],
            ctx.policy.act_spec(layer.act_unsigned),
            use_pallas=ctx.policy.use_pallas,
            reduce_axis=_tp_reduce_axis(layer),
        )
        b = params.get("b_q")
        if b is not None:
            # int32 bias folded at the dequantized output scale (eq. 20)
            b = b.astype(jnp.float32) * params["b_scale"]
            y = y + b.astype(y.dtype)
            b = None
    else:
        raise ValueError(f"unknown quant mode {ctx.mode}")
    if b is not None:
        y = y + b
    return y


def _expert_capacity_forward(layer, params: dict, x: jax.Array,
                             ctx: Optional[QuantCtx]):
    """The training forward's capacity layout: x (..., E, C, in) @ w
    (E, in, out) -> (..., E, C, out), each expert's activations at its
    own threshold.  Calibration routes dropless, never here."""
    ein = "...ecd,edf->...ecf"
    if ctx is None or not ctx.enabled(layer):
        return jnp.einsum(ein, x, params["w"])
    spec = ctx.policy.act_spec()
    astate = ctx.qparams[layer.path]["act"]
    if ctx.mode == "fake":
        # one activation threshold per expert: the layer's own fake
        # quantizer mapped over the E axis
        e_axis = x.ndim - 3
        xq = jax.vmap(lambda xe, st: _fq_act(xe, st, spec),
                      in_axes=(e_axis, 0), out_axes=e_axis)(x, astate)
        wq = _fq_weight(params["w"], ctx.qparams[layer.path]["w"],
                        ctx.policy.weight_spec())
        return jnp.einsum(ein, xq.astype(x.dtype), wq)
    # one activation threshold per expert, broadcast along the E axis
    astate = {k: v[:, None, None] for k, v in astate.items()}
    if ctx.mode == "int8":
        t_adj = jnp.maximum(
            Q.adjusted_threshold(astate["t_max"], astate["alpha"], spec), 1e-8)
        s_x = (spec.levels / t_adj).astype(jnp.float32)     # (E, 1, 1)
        x_int = jnp.clip(jnp.round(x * s_x), spec.qmin,
                         spec.qmax).astype(jnp.int8)
        # (..., E, C, in) @ (E, in, out) with int32 accumulation
        acc = jnp.einsum(ein, x_int, params["w_q"],
                         preferred_element_type=jnp.int32)
        scale = params["w_scale"][:, None, :] / s_x           # (E, 1, out)
        return (acc.astype(jnp.float32) * scale).astype(x.dtype)
    raise ValueError(f"expert layers calibrate on the dropless layout, "
                     f"not the capacity layout (mode {ctx.mode!r})")


def expert_dense_forward(layer, params: dict, x: jax.Array,
                         ctx: Optional[QuantCtx], groups):
    """x: (M, in) rows grouped by expert (``groups``: contiguous groups of
    ``groups.sizes`` rows, in expert order) @ w: (E, in, out) -> (M, out).

    Activation thresholds are per expert, shape (E,): calibration folds
    only each expert's routed rows into its observer (a tile's padding
    rows never), and every row quantizes with its expert's threshold.
    The int8 path runs the ``expert_matmul`` kernel under
    ``policy.use_pallas``, else the same grouped contraction in jnp
    (``lax.ragged_dot``, int32 accumulation), which is the kernel's
    oracle.  Without ``groups``: ``_expert_capacity_forward``."""
    if groups is None:
        return _expert_capacity_forward(layer, params, x, ctx)
    sizes = groups.sizes
    if ctx is None or not ctx.enabled(layer):
        return jax.lax.ragged_dot(x, params["w"], sizes)
    spec = ctx.policy.act_spec()
    if ctx.mode == "calibrate":
        ctx.updates[layer.path] = calib.update_expert_observer(
            ctx.qparams[layer.path]["act"], x, groups.row_expert,
            groups.row_valid, layer.num_experts, kind=ctx.policy.observer)
        return jax.lax.ragged_dot(x, params["w"], sizes)
    astate = ctx.qparams[layer.path]["act"]
    if ctx.mode == "fake":
        qs = ctx.qparams[layer.path]
        rows = {k: v[groups.row_expert][:, None] for k, v in astate.items()}
        if spec.symmetric:
            xq = Q.fake_quant_symmetric(x, rows["t_max"], rows["alpha"], spec)
        else:
            xq = Q.fake_quant_asymmetric(x, rows["t_l"], rows["t_r"],
                                         rows["alpha_t"], rows["alpha_r"],
                                         spec)
        wq = _fq_weight(params["w"], qs["w"], ctx.policy.weight_spec())
        return jax.lax.ragged_dot(xq.astype(x.dtype), wq, sizes)
    if ctx.mode == "int8":
        t_adj = jnp.maximum(
            Q.adjusted_threshold(astate["t_max"], astate["alpha"], spec), 1e-8)
        s_x = (spec.levels / t_adj).astype(jnp.float32)          # (E,)
        # the combined per-(expert, out-channel) dequant scale
        scale = (params["w_scale"] / s_x[:, None]).astype(jnp.float32)
        if ctx.policy.use_pallas:
            from repro.kernels import ops as kops

            return kops.expert_matmul(
                x, params["w_q"], scale, s_x, groups.tile_expert,
                groups.n_live, block_m=groups.block_m, bits=spec.bits,
            ).astype(x.dtype)
        from repro.kernels import ref as kref

        return kref.expert_matmul_ref(
            x, params["w_q"], scale, s_x, groups.row_expert, sizes,
            bits=spec.bits).astype(x.dtype)
    raise ValueError(ctx.mode)


def _tp_reduce_axis(layer):
    """Mesh axis a row-parallel layer must psum over, or None.

    Only consults the trace-time shard context (repro.shard.context) —
    the unsharded engine never pays an import or a branch.  Row-parallel
    roles are identified by the layer's logical input axis: 'heads'
    (attention wo) and 'mlp' (ffn down/fc2) are the projections whose
    contraction dimension is split across tensor-parallel shards.
    """
    try:
        from repro.shard.context import tp_shard_info
    except ImportError:
        return None
    info = tp_shard_info()
    if info is None:
        return None
    axes = getattr(layer, "logical_axes", None)
    if axes and axes[0] in ("heads", "mlp"):
        return info.axis
    return None


def _int8_matmul(x, w_q, w_scale, astate, aspec, *, use_pallas=False,
                 reduce_axis=None):
    """int8 x int8 -> int32 -> dequant.  Static activation threshold.

    The XLA path (dot_general with int32 accumulation) maps onto the MXU's
    native int8 pipeline on TPU; the Pallas kernel (kernels/quant_matmul)
    additionally fuses the per-channel dequant epilogue and is selected on
    real hardware via policy.use_pallas.

    ``reduce_axis`` (tensor-parallel row epilogue) psums the int32
    accumulators over that mesh axis BEFORE the single dequant: integer
    addition is exact, so the sharded product is bit-identical to the
    unsharded one and the wire carries int32, never f32
    (dist/collectives.py::compressed_psum's integer fast path).  The
    fused Pallas path cannot host a mid-kernel collective, so a reduced
    matmul always takes the XLA path.
    """
    t_adj = jnp.maximum(
        Q.adjusted_threshold(astate["t_max"], astate["alpha"], aspec), 1e-8
    )
    s_x = aspec.levels / t_adj
    if use_pallas and reduce_axis is None and jnp.ndim(s_x) == 0:
        # raw activations + act_scale: the kernel's fused VPU quantize does
        # the round/clip in VMEM (quantizing here first would round twice
        # and stream an extra tensor through HBM)
        from repro.kernels import ops as kops

        lead = x.shape[:-1]
        y = kops.quant_matmul(
            x.reshape(-1, x.shape[-1]),
            w_q,
            (w_scale / s_x).astype(jnp.float32),
            s_x.astype(jnp.float32),
        )
        return y.reshape(*lead, -1).astype(x.dtype)
    x_int = jnp.clip(jnp.round(x * s_x), aspec.qmin, aspec.qmax).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_int,
        w_q,
        (((x_int.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    if reduce_axis is not None:
        from repro.dist.collectives import compressed_psum

        acc = compressed_psum(acc, reduce_axis, mean=False)
    scale = (w_scale / s_x).astype(jnp.float32)
    return (acc.astype(jnp.float32) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# int8 model conversion (serving path)
# ---------------------------------------------------------------------------


def _quantize_weight(w, wstate: dict, spec: Q.QuantSpec):
    """(w_q int8, w_scale float32) of one weight at its thresholds."""
    w_q, s = _quantize_weight_fused(w, wstate, spec)
    # the small scale stays out of the fused program, where XLA would
    # fold 1 / (levels / t) into t / levels, rounded differently
    w_scale = 1.0 / s
    if spec.per_channel:
        w_scale = jnp.squeeze(w_scale, axis=-2)
    return w_q, w_scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=2)
def _quantize_weight_fused(w, wstate: dict, spec: Q.QuantSpec):
    """(w_q, s): one fused program, so the float32 copies of ``w`` are
    never materialized (eager, they held four times the weight's bytes at
    once)."""
    w = w.astype(jnp.float32)
    if "pointwise" in wstate:
        w = Q.apply_pointwise_scale(w, wstate["pointwise"])
    t = wstate["t_max"]
    alpha = wstate["alpha"]
    if spec.per_channel:
        shape = _weight_threshold_shape(w)
        t = t.reshape(shape)
        alpha = alpha.reshape(shape)
    t_adj = jnp.maximum(Q.adjusted_threshold(t, alpha, spec), 1e-8)
    s = spec.levels / t_adj
    w_q = jnp.clip(jnp.round(w * s), spec.qmin, spec.qmax).astype(jnp.int8)
    return w_q, s


def convert_to_int8(model, params: dict, qparams: dict, policy: QuantPolicy) -> dict:
    """Replace every quantized Dense/ExpertDense 'w' with int8 + scales.

    Produces the *serving* parameter pytree: quantized weights live in
    memory as int8 (half/quarter the HBM bytes — the inference speedup the
    paper targets), biases as int32 at the combined scale (eq. 20).
    """
    out = jax.tree.map(lambda x: x, params)  # structural copy
    spec = policy.weight_spec()
    for layer, lp in _quant_layers_with_params(model, out, policy):
        if layer.path not in qparams:
            continue
        lp["w_q"], lp["w_scale"] = _quantize_weight(
            lp.pop("w"), qparams[layer.path]["w"], spec)
        if "b" in lp:
            astate = qparams[layer.path]["act"]
            aspec = policy.act_spec(layer.act_unsigned)
            t_a = jnp.maximum(
                Q.adjusted_threshold(astate["t_max"], astate["alpha"], aspec),
                1e-8,
            )
            act_scale = t_a / aspec.levels
            # stacked layers: (L,) act scale broadcasts against (L, C)
            # per-channel weight scales
            if act_scale.ndim < lp["w_scale"].ndim:
                act_scale = act_scale.reshape(
                    act_scale.shape
                    + (1,) * (lp["w_scale"].ndim - act_scale.ndim))
            b = lp.pop("b")
            lp["b_q"] = Q.quantize_bias_int32(
                b.astype(jnp.float32), act_scale, lp["w_scale"]
            )
            lp["b_scale"] = (act_scale * lp["w_scale"]).astype(jnp.float32)
    return out
