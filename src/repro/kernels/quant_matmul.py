"""Pallas TPU kernel: fused int8 serving matmul.

The paper's inference speedup comes from int8 execution; on TPU v5e the MXU
runs int8 at 2x bf16 throughput *and* int8-resident weights halve the HBM
stream.  This kernel implements the whole FAT serving contraction in one
pass over VMEM tiles:

    x_bf16 --quantize(static threshold)--> x_int8
    acc_int32 = x_int8 @ w_int8                        (MXU)
    out = acc * (w_scale * t_a / (levels_a * levels_w)) (VPU epilogue)

Grid is (M/bm, N/bn, K/bk) with a VMEM int32 accumulator tile; K is the
innermost ("arbitrary") dimension so each (i, j) output tile accumulates
across K-steps without re-materializing.  Tile defaults are MXU-aligned
(multiples of 128 in the lane dim; int8 native VMEM tiling is (32, 128)).

Weights arrive pre-quantized (int8) with per-output-channel dequant scales —
the paper's vector mode (§3.1.5).  Activation quantization uses the static
calibrated+trained threshold (§2: thresholds computed beforehand so nothing
is "calculated on the fly" at serving time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_int4


def _kernel(x_ref, w_ref, sw_ref, sa_ref, o_ref, acc_ref, *, n_k: int,
            qmin: float, qmax: float, w_bits: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fused activation quantization (VPU) — static threshold scale;
    # activations stay int8 regardless of the weight width
    s_a = sa_ref[0, 0]
    x = x_ref[...].astype(jnp.float32) * s_a
    x_q = jnp.clip(jnp.round(x), qmin, qmax).astype(jnp.int8)

    w = w_ref[...]
    if w_bits == 4:
        # int4 weights ride packed along K (two K rows per stored byte);
        # one VMEM unpack restores the (bk, bn) int8 tile the MXU eats
        w = unpack_int4(w, axis=0)

    # int8 x int8 -> int32 on the MXU (int4 weights are int8-resident
    # values in [-7, 7] after the unpack)
    acc_ref[...] += jax.lax.dot_general(
        x_q, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        # per-out-channel dequant: w_scale already folds 1/s_a at call site
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * sw_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "w_bits"),
)
def quant_matmul(
    x: jax.Array,        # (M, K) float (bf16/f32) activations
    w_q: jax.Array,      # (K, N) int8 weights — (K/2, N) packed at w_bits=4
    w_scale: jax.Array,  # (N,) f32 combined dequant scale (already / s_a)
    act_scale: jax.Array,  # scalar f32: levels / T_adj (quantization scale)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
    w_bits: int = 8,
):
    """Fused quantize -> int8 matmul -> dequant.

    K and N (weight dims) tile by ``_weight_tile``: lane-aligned divisors
    up to ``block_k``/``block_n``, else the whole dim.  M is the token dim
    and ragged at decode (M = B*1); it is padded up to a sublane-aligned
    tile and the pad rows sliced off, so the same kernel serves prefill
    (M large) and decode (M = 1..8).

    ``w_bits == 4``: weights arrive nibble-packed along K (pack_int4 on
    axis 0 — half the resident weight bytes), quantized to ±7 with the
    per-channel ``w_scale`` carrying T/7; the kernel unpacks each VMEM
    tile before the MXU dot, so the N BlockSpec and the (N,) scale
    mapping are untouched.
    """
    m0, k = x.shape
    k2, n = w_q.shape
    assert k == k2 * (2 if w_bits == 4 else 1), (x.shape, w_q.shape, w_bits)
    # M tiling: sublane-align (f32 min tile is (8, 128)), then prefer an
    # exact-divisor tile (zero pad rows) but never shrink below a quarter
    # of block_m — a tiny bm turns the MXU matmul into a long sequential
    # grid.  When no acceptable divisor exists, pad to a block_m multiple
    # (waste < bm rows, amortized at the prefill Ms where this fires).
    m_aligned = -(-m0 // 8) * 8
    bm_cap = max(8, min(block_m, m_aligned) // 8 * 8)
    bm = next(
        (c for c in range(bm_cap, max(8, bm_cap // 4) - 1, -8)
         if m_aligned % c == 0),
        bm_cap,
    )
    m = -(-m_aligned // bm) * bm
    if m != m0:
        x = jnp.pad(x, [(0, m - m0), (0, 0)])
    bn, bk = _weight_tile(n, block_n), _weight_tile(k, block_k)
    # packed weights halve the K BlockSpec dim: tile kk covers logical
    # rows [kk*bk, (kk+1)*bk) either way, so the index map is unchanged
    bkw = bk // 2 if w_bits == 4 else bk
    assert bkw * (2 if w_bits == 4 else 1) == bk, (
        f"int4 weight K tile must be even, got bk={bk}")
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    kernel = functools.partial(_kernel, n_k=n_k, qmin=-127.0, qmax=127.0,
                               w_bits=w_bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkw, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="quant_matmul",
    )(x, w_q, w_scale.reshape(1, n).astype(jnp.float32),
      jnp.reshape(act_scale, (1, 1)).astype(jnp.float32))[:m0]


def _weight_tile(dim: int, cap: int) -> int:
    """Tile for a weight dim (K or N): the largest multiple of the lane
    width (128) that divides ``dim`` and is <= ``cap``; where none exists
    (576 = 4.5 * 128, 192) the whole dim, which Mosaic always accepts as
    a block extent."""
    for t in range(cap // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim
