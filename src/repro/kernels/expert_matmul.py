"""Pallas TPU kernel: grouped int8 expert matmul (dropless MoE).

The rows arrive grouped by expert (``repro.models.moe.group_rows``):
``block_m``-row tiles, each holding rows of ONE expert, the experts in
order, an expert with no rows holding no tile.  One call multiplies every
tile by its own expert's int8 weights:

    x_tile --quantize(the expert's static threshold)--> int8
    acc_int32 = x_int8 @ w_int8[expert]                  (MXU)
    out = acc * w_scale[expert]                          (VPU epilogue)

so a token's ``top_k`` experts each see it, with no capacity and no
padding beyond each group's last tile.

Grid and DMA
------------
``(N / block_n, tiles)`` with the tiles innermost.  K is whole in every
block: a step reads one (block_m, K) row tile and the (K, block_n) weight
tile of the tile's expert, and writes one finished (block_m, block_n)
output tile, with no accumulator carried across steps.  The tile ->
expert map and the number of live tiles ride the scalar-prefetch path
(SMEM) and steer the index maps.  Consecutive tiles of one expert map to
the same weight block, so the pipeline elides the copy: each expert's
weights are read once per column tile, once per call where one column
tile spans N (the usual case: ``_col_tile``).

Dead tiles
----------
The layout has a static number of tiles, enough for any routing; the
tiles past ``n_live`` hold no row.  Their indices clamp to the last live
tile, so their copies are elided, and ``pl.when(t < n_live)`` skips their
compute: the DMA clamp and compute skip of the decode attention kernel.
Their output rows are left unwritten; nothing reads them.

Scales
------
Activations quantize with one threshold per expert: ``act_scale`` (E,)
= levels / T_adj sits whole in SMEM and is read as a scalar.  The
per-(expert, out-channel) dequant scales (already divided by the
expert's activation scale) arrive as (E, 1, N), so their block (1, 1,
block_n) keeps its last two dims legal.  The pure-jnp oracle is
kernels/ref.py::expert_matmul_ref (``lax.ragged_dot`` over the same
layout), which the program also runs off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one weight tile (K, block_n) int8: double-buffered it stays
# well inside the 16 MiB of scoped VMEM beside the row and output tiles
W_TILE_BYTES = 4 << 20


def _col_tile(k: int, n: int) -> int:
    """The whole N where a (K, N) int8 tile fits ``W_TILE_BYTES``, else the
    largest multiple of 128 dividing N that does (N itself if none)."""
    if k * n <= W_TILE_BYTES:
        return n
    for t in range(min(n, W_TILE_BYTES // k) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _tile(t, n_live):
    """A grid tile's layout tile: the dead tail repeats the last live."""
    return jnp.minimum(t, jnp.maximum(n_live[0] - 1, 0))


def _kernel(tile_e_ref, n_live_ref, x_ref, w_ref, sw_ref, sa_ref, o_ref, *,
            levels: float):
    t = pl.program_id(1)

    @pl.when(t < n_live_ref[0])
    def _tile_body():
        e = tile_e_ref[t]
        x = x_ref[...].astype(jnp.float32) * sa_ref[e]
        x_q = jnp.clip(jnp.round(x), -levels, levels).astype(jnp.int8)
        acc = jax.lax.dot_general(
            x_q, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        o_ref[...] = (acc.astype(jnp.float32) * sw_ref[0]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "bits", "out_dtype", "interpret"))
def expert_matmul(
    x: jax.Array,          # (n_tiles * block_m, K) float rows, grouped
    w_q: jax.Array,        # (E, K, N) int8
    w_scale: jax.Array,    # (E, N) f32 dequant scale, already / act scale
    act_scale: jax.Array,  # (E,) f32 levels / T_adj per expert
    tile_expert: jax.Array,  # (n_tiles,) int32 expert of each tile
    n_live: jax.Array,     # () int32 tiles holding rows
    *,
    block_m: int,
    bits: int = 8,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    """Grouped int8 matmul over expert-grouped row tiles -> (rows, N)."""
    m, k = x.shape
    e, k2, n = w_q.shape
    assert k == k2 and m % block_m == 0, (x.shape, w_q.shape, block_m)
    n_tiles = m // block_m
    assert tile_expert.shape == (n_tiles,), (tile_expert.shape, n_tiles)
    bn = _col_tile(k, n)

    def rows(j, t, te, nl):
        return (_tile(t, nl), 0)

    def expert_cols(j, t, te, nl):
        return (te[_tile(t, nl)], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((block_m, k), rows),
            pl.BlockSpec((1, k, bn), expert_cols),
            pl.BlockSpec((1, 1, bn), expert_cols),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_m, bn), lambda j, t, te, nl: (_tile(t, nl), j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, levels=float(2 ** (bits - 1) - 1)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(n_live, (1,)).astype(jnp.int32),
      x, w_q, w_scale.reshape(e, 1, n).astype(jnp.float32),
      act_scale.reshape(e).astype(jnp.float32))
