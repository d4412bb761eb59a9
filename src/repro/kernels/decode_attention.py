"""Pallas TPU kernel: fused int8-KV flash-decode attention.

One decode step reads the whole KV cache once — at production shapes the
step is purely HBM-bandwidth-bound, which is why the cache is stored int8
(half the bytes of bf16).  This kernel keeps the int8 stream all the way
into VMEM and runs the one-token flash-decode online softmax in a single
pass:

    k_tile_int8 --DMA--> VMEM --dequant(per-head static scale)--> f32
    s   = (q * k_scale / sqrt(D)) @ k_tile^T          (MXU)
    m,l = running max / normalizer update             (VPU)
    acc = acc * exp(m_old - m_new) + softmax_tile @ v_tile
    out = acc * v_scale / l                           (epilogue)

Tiles and block tables
----------------------
The kernel core (``decode_attention_tiles``) reads KV through a **block
table**: K/V arrive as a page pool ``(pages, block_s, KV, D)`` and a
``(B, S/block_s)`` int32 table maps each (batch row, logical block) to a
pool page via a scalar-prefetch index map — the table rides in SMEM and
steers the DMA engine, costing nothing on the compute path.  The paged
cache (repro.cache.paged) passes its pool/table straight through; the
dense entry point ``decode_attention_int8`` reshapes its contiguous
cache into pool form (a free leading-axis split) and passes the identity
table — so dense and paged layouts share ONE kernel body and the table
is always data, never shape.

Grid layout
-----------
``(B, S/block_s)`` with the sequence dimension innermost and declared
"arbitrary" (B is "parallel"): sequential execution along the sequence
axis is what lets the accumulators live in VMEM scratch across sequence
steps — the partial-max/partial-sum combine of flash-decode.  Each step
DMAs one ``(1, block_s, KV, D)`` tile holding EVERY KV head and loops
over the heads in the kernel body (a static unroll).  The head axis is
not a grid axis because Mosaic only accepts a block whose last two dims
are (8, 128)-aligned or span the array: a one-head ``(1, block_s, 1, D)``
block over the ``(pages, block_s, KV, D)`` pool is neither, so every
layout of repro.cache keeps its storage order and the kernel takes the
heads together.  Per-head dequant scales fold into q (keys) and the
epilogue (values), so dequantization costs one scalar multiply per tile
element, on the VPU, overlapping the MXU contraction.

Dead tiles: DMA clamp and compute skip
--------------------------------------
The block table and the (B,) ``cur_pos`` vector are the two scalar-
prefetch operands, so the K/V index maps see each row's position.  A
tile that starts at or past ``cur_pos[b]`` holds no live key.  Its index
is clamped to the row's last live tile, ``max(ceil(cur_pos[b] /
block_s) - 1, 0)``: the block index then repeats the previous grid
step's and the pipeline elides the copy.  In the body, ``pl.when(si *
block_s < cur_pos[b])`` skips the tile's compute, the same predicate.  A
row costs the DMA and the MXU work of its live tiles only; a dead grid
step is a fixed per-step overhead.  The table is read only at live
entries, so a paged table may hold anything past a row's last live
block.  Skipping changes no output bit: a fully masked tile would leave
the running max, normalizer and accumulator as they were (``corr ==
exp(0) == 1``, ``p == 0``).

Scales
------
The (KV,) scales sit whole in SMEM and are read as scalars
(``ks_ref[h]``): a ``(1, 1)`` VMEM block over a ``(KV, 1)`` array breaks
the same block-shape rule.

VMEM scratch expectations
-------------------------
Three scratch buffers persist across the innermost grid axis: the
(KV, G, D) f32 output accumulator plus (KV, G, 1) running max and
normalizer.  They are (re)initialized at ``si == 0`` and flushed to the
output ref at ``si == n_s - 1`` whether or not those tiles are live (a
skipped tile leaves them untouched) — correctness relies on the
innermost axis running in-order on one core, which the "arbitrary"
dimension semantics guarantee.  Budget: one K tile + V tile of
(block_s, KV, D) int8 are resident per step alongside the scratch;
block_s is chosen so a whole tile fits comfortably (``decode_block_s``,
default 128; the paged layout uses its page size).

Masking semantics
-----------------
``cur_pos`` is the number of valid cache slots per batch row — a scalar
(uniform batch, the single-stream serving path) or a (B,) vector (the
slot-based continuous-batching scheduler: each slot of the batch decodes
at its own position).  Positions are LOGICAL (block index * block_s +
offset) — the table only relocates storage.  Within the last live tile,
slots at ``k_pos >= cur_pos[b]`` are masked BEFORE the running-max
update and re-masked after.  A row with ``cur_pos[b] == 0`` (inactive
scheduler slot) skips every tile, ends with ``acc == 0, l == 0`` and
normalizes to exact zeros in the epilogue; the partials kernel returns
``(0, NEG_INF, 0)`` for it, the merge identity.

Sliding window
--------------
A static ``window`` (a windowed layer's width; None for a full layer,
which compiles to the kernel without it) also bounds each row from the
left: its query at position ``cur_pos[b] - 1`` sees keys from
``cur_pos[b] - window`` on.  Tiles left of that clamp to the row's
first live tile, ``max(cur_pos[b] - window, 0) // block_s``, so their
copies are elided as the dead tail's are; ``pl.when`` skips their
compute, and the edge tile masks the keys left of the window.  The
cache still holds every position (dense or paged): the window is a mask
over it.

A bf16 cache runs through the same kernel with scales == 1.  The
pure-jnp oracle is kernels/ref.py::decode_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_int4

NEG_INF = -1e30
# f32 MXU passes for the score and value dots, so the kernel computes
# what its f32 oracle (kernels/ref.py) computes: with Mosaic's default,
# one bf16 pass, the output is 2.2e-3 of max |output| off the oracle on
# a v5e, with these passes 7e-7.  The extra passes' time is not measured
_F32 = jax.lax.Precision.HIGHEST


def decode_block_s(s: int, block_s: int = 128) -> int:
    """The sequence tile of the dense entry points over a cache of ``s``
    positions: the largest multiple of 8 up to ``block_s`` that divides
    ``s`` (8 if none does).  The scheduler counts live tiles with it."""
    bs = max(8, min(block_s, s) // 8 * 8)
    while bs > 8 and s % bs:
        bs -= 8
    return bs


def _kv_index(bi, si, tab, pos, *, block_s: int, n_s: int, window=None):
    """K/V index map: row ``bi``'s page for tile ``si``, clamped to the
    row's last live tile ``max(ceil(pos / block_s) - 1, 0)`` and, with a
    window, to its first, ``max(pos - window, 0) // block_s``.  A dead
    tile repeats a neighbouring grid step's page, so its copy is elided,
    and the table is never read outside a row's live blocks."""
    last = jnp.minimum(n_s - 1, (jnp.maximum(pos[bi], 1) - 1) // block_s)
    if window is None:
        return (tab[bi, jnp.minimum(si, last)], 0, 0, 0)
    first = jnp.maximum(pos[bi] - window, 0) // block_s
    return (tab[bi, jnp.clip(si, first, last)], 0, 0, 0)


def _flash_step(q_ref, k_ref, v_ref, ks_ref, pos_ref, acc_ref, m_ref,
                l_ref, *, bi, si, block_s: int, dim: int, kv_bits: int,
                window=None):
    """One online-softmax tile update for every KV head of the tile
    (shared by the normalized kernel and the sequence-parallel partials
    kernel — same math up to, but not including, the epilogue)."""

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a tile with no live key is skipped: its index map repeated a live
    # tile's, so its contents are not this tile's
    live = si * block_s < pos_ref[bi]
    if window is not None:
        live &= (si + 1) * block_s > pos_ref[bi] - window

    @pl.when(live)
    def _tile():
        # mask the unwritten tail (cache slots >= this row's cur_pos),
        # per batch row so slot-ragged positions mask per slot.  k_pos
        # is the LOGICAL position — the block table only moves storage
        k_pos = si * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        valid = k_pos < pos_ref[bi]
        if window is not None:
            valid &= k_pos >= pos_ref[bi] - window
        inv_sqrt_d = jax.lax.rsqrt(jnp.asarray(dim, jnp.float32))

        for h in range(q_ref.shape[1]):
            # fold the key dequant scale and 1/sqrt(D) into q: per-head
            # scales are uniform within the head, so (q*c) @ k_int8 ==
            # c * (q @ k)
            q = q_ref[0, h].astype(jnp.float32) * (ks_ref[h] * inv_sqrt_d)
            k = k_ref[0, :, h, :]                    # (bs, D) — D/2 packed
            if kv_bits == 4:
                # the ONE extra op of the int4 lane: nibbles -> int8 in
                # VMEM, before the f32 cast the int8 path already does.
                # Scales carry T/7 instead of T/127, so the fold is
                # unchanged.
                k = unpack_int4(k, axis=-1)
            k = k.astype(jnp.float32)                # (bs, D) dequant-free
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )                                        # (G, bs)
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[h]                        # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # re-mask: a masked key adds exactly 0 whatever m_new is
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (G, bs)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, :, h, :]                    # (bs, D)
            if kv_bits == 4:
                v = unpack_int4(v, axis=-1)
            v = v.astype(jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )
            m_ref[h] = m_new


def _kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            acc_ref, m_ref, l_ref, *, n_s: int, block_s: int, dim: int,
            kv_bits: int, window=None):
    # tab_ref is the scalar-prefetch block table: consumed by the K/V
    # index maps (page steering), never by the compute body
    del tab_ref
    si = pl.program_id(1)
    _flash_step(q_ref, k_ref, v_ref, ks_ref, pos_ref, acc_ref, m_ref,
                l_ref, bi=pl.program_id(0), si=si, block_s=block_s,
                dim=dim, kv_bits=kv_bits, window=window)

    @pl.when(si == n_s - 1)
    def _epilogue():
        # value dequant folds once into the epilogue (linear in v)
        for h in range(o_ref.shape[1]):
            o = acc_ref[h] * vs_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = o.astype(o_ref.dtype)


def _partials_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     oa_ref, om_ref, ol_ref, acc_ref, m_ref, l_ref, *,
                     n_s: int, block_s: int, dim: int, kv_bits: int):
    """Sequence-parallel epilogue: emit the raw flash state (unnormalized
    accumulator — value-dequantized, since v_scale is linear in v — plus
    running max and normalizer) instead of normalizing.  One shard of a
    sequence-split cache runs this over its LOCAL tiles; the cross-shard
    merge (repro.shard.partial_softmax.sp_partial_combine) produces the
    exact unsharded softmax from the gathered (m, l, acc) triples."""
    del tab_ref
    si = pl.program_id(1)
    _flash_step(q_ref, k_ref, v_ref, ks_ref, pos_ref, acc_ref, m_ref,
                l_ref, bi=pl.program_id(0), si=si, block_s=block_s,
                dim=dim, kv_bits=kv_bits)

    @pl.when(si == n_s - 1)
    def _epilogue():
        for h in range(oa_ref.shape[1]):
            oa_ref[0, h] = (acc_ref[h] * vs_ref[h]).astype(oa_ref.dtype)
        om_ref[0] = m_ref[...].astype(om_ref.dtype)
        ol_ref[0] = l_ref[...].astype(ol_ref.dtype)


def _launch(kernel, name, out_specs, out_shape, q, k_pool, v_pool,
            block_tab, k_scale, v_scale, cur_pos, *, interpret, kv_bits,
            window=None):
    """One decode launch over block-table-mapped tiles: grid, specs and
    operands shared by the normalized and the partials kernel."""
    b, kvh, g, d = q.shape
    dp = k_pool.shape[-1]  # storage width (D, or D/2 packed)
    assert dp * (2 if kv_bits == 4 else 1) == d, (
        f"kv_bits={kv_bits}: pool head dim {dp} does not match q head "
        f"dim {d}")
    bs = k_pool.shape[1]
    n_s = block_tab.shape[1]

    # a window reaches the index map and the kernel body only where it
    # is set, so a full layer compiles to the kernel without one
    win = {} if window is None else {"window": window}
    kv_tile = pl.BlockSpec((1, bs, kvh, dp), functools.partial(
        _kv_index, block_s=bs, n_s=n_s, **win))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_s),
        in_specs=[_head_block(kvh, g, d), kv_tile, kv_tile, smem, smem],
        out_specs=out_specs,
        scratch_shapes=_scratch(kvh, g, d),
    )
    return pl.pallas_call(
        functools.partial(kernel, n_s=n_s, block_s=bs, dim=d,
                          kv_bits=kv_bits, **win),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(block_tab.astype(jnp.int32),
      # the per-row valid-key count: a scalar broadcasts to every row
      jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,)),
      q, k_pool, v_pool,
      k_scale.reshape(-1).astype(jnp.float32),
      v_scale.reshape(-1).astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "interpret", "kv_bits", "window"))
def decode_attention_tiles(
    q: jax.Array,          # (B, KV, G, D) float — one query token, GQA view
    k_pool: jax.Array,     # (pages, block_s, KV, D) int8/float (D/2 packed
    v_pool: jax.Array,     # (pages, block_s, KV, D)   bytes at kv_bits=4)
    block_tab: jax.Array,  # (B, n_blocks) int32 page per (row, logical blk)
    k_scale: jax.Array,    # (KV,) f32 per-head dequant scale
    v_scale: jax.Array,    # (KV,) f32 per-head dequant scale
    cur_pos: jax.Array,    # int32 valid-slot count: scalar or per-slot (B,)
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
    kv_bits: int = 8,
    window: int | None = None,
):
    """Kernel core: fused one-token decode over block-table-mapped KV
    tiles.  The dense layout passes a free reshape of its cache plus the
    identity table (``decode_attention_int8``); the paged layout passes
    its pool/table directly — same compiled kernel either way.

    ``kv_bits == 4``: K/V tiles hold packed nibbles (D/2 bytes wide) and
    the kernel body unpacks them in VMEM right before the f32 cast.  The
    block table and index maps are UNCHANGED — they address blocks, not
    bytes; only the tile's last BlockSpec dim halves."""
    b, kvh, g, d = q.shape
    return _launch(
        _kernel, "decode_attention", _head_block(kvh, g, d),
        jax.ShapeDtypeStruct((b, kvh, g, d), out_dtype),
        q, k_pool, v_pool, block_tab, k_scale, v_scale, cur_pos,
        interpret=interpret, kv_bits=kv_bits, window=window)


def _dense_pool(k_cache, v_cache, block_s: int):
    """Contiguous (B, S, KV, D) caches as a page pool and its identity
    block table: splitting the sequence axis into (blocks, block_s)
    merges with batch into a page axis copy-free."""
    b, s, kvh, d = k_cache.shape
    # a tile that divides S exactly: a pad here copies the WHOLE cache
    # every decode step (it cannot be hoisted out of a scanned decode
    # loop), which would double the HBM traffic the int8 cache exists to
    # halve.  The scheduler rounds the cache length to a block_s multiple
    # for the kernel path; the pad fallback below only fires for odd
    # ad-hoc lengths.
    bs = decode_block_s(s, block_s)
    s_pad = -(-s // bs) * bs
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    n_s = s_pad // bs
    tab = jnp.arange(b * n_s, dtype=jnp.int32).reshape(b, n_s)
    return (k_cache.reshape(b * n_s, bs, kvh, d),
            v_cache.reshape(b * n_s, bs, kvh, d), tab)


@functools.partial(
    jax.jit,
    static_argnames=("block_s", "out_dtype", "interpret", "kv_bits",
                     "window"))
def decode_attention_int8(
    q: jax.Array,        # (B, KV, G, D) float — one query token, GQA view
    k_cache: jax.Array,  # (B, S, KV, D) int8 (or float with scales == 1;
    v_cache: jax.Array,  # (B, S, KV, D)  D/2 packed bytes at kv_bits=4)
    k_scale: jax.Array,  # (KV,) f32 per-head dequant scale
    v_scale: jax.Array,  # (KV,) f32 per-head dequant scale
    cur_pos: jax.Array,  # int32 valid-slot count: scalar or per-slot (B,)
    *,
    block_s: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
    kv_bits: int = 8,
    window: int | None = None,
):
    """Dense entry point: contiguous (B, S, KV, D) caches degenerate to
    the identity block table over a leading-axis reshape of the same
    buffer — the kernel body is shared with the paged layout.

    ``cur_pos`` broadcasts to a per-batch-row (B,) valid-slot vector (the
    prefill kernel's per-request ``kv_len`` pattern): a scalar serves the
    uniform single-stream path, a vector serves slot-ragged continuous
    batching, where a 0 entry marks an inactive slot (output zeros).
    """
    k_pool, v_pool, tab = _dense_pool(k_cache, v_cache, block_s)
    return decode_attention_tiles(
        q, k_pool, v_pool, tab, k_scale, v_scale, cur_pos,
        out_dtype=out_dtype, interpret=interpret, kv_bits=kv_bits,
        window=window)


def _head_block(kvh, g, last):
    """Per-batch-row block over a (B, KV, G, last) array: all heads."""
    return pl.BlockSpec((1, kvh, g, last),
                        lambda bi, si, tab, pos: (bi, 0, 0, 0))


def _scratch(kvh, g, d):
    return [
        pltpu.VMEM((kvh, g, d), jnp.float32),  # output accumulator
        pltpu.VMEM((kvh, g, 1), jnp.float32),  # running max
        pltpu.VMEM((kvh, g, 1), jnp.float32),  # running normalizer
    ]


@functools.partial(jax.jit, static_argnames=("interpret", "kv_bits"))
def decode_attention_partials_tiles(
    q: jax.Array,          # (B, KV, G, D) float — one query token, GQA view
    k_pool: jax.Array,     # (pages, block_s, KV, D) int8/float
    v_pool: jax.Array,     # (pages, block_s, KV, D)
    block_tab: jax.Array,  # (B, n_blocks) int32
    k_scale: jax.Array,    # (KV,) f32
    v_scale: jax.Array,    # (KV,) f32
    cur_pos: jax.Array,    # int32 valid-slot count: scalar or per-slot (B,)
    *,
    interpret: bool = False,
    kv_bits: int = 8,
):
    """Partial-softmax variant of ``decode_attention_tiles`` for the
    sequence-parallel engine: same grid, same block specs, same online-
    softmax body, but the epilogue emits the raw flash state —
    (acc, m, l) with acc UNNORMALIZED (already v-dequantized) — so a
    shard holding a slice of the S axis can hand its partials to the
    cross-shard tree merge.  ``cur_pos`` here counts the valid slots IN
    THIS POOL (the caller clips the global count to its local slice);
    a shard with nothing visible returns (0, NEG_INF, 0) — the merge
    identity.  Returns ((B, KV, G, D) f32, (B, KV, G) f32, (B, KV, G)
    f32).  The single-shard invariant ``acc / max(l, eps) ==
    decode_attention_tiles(...)`` is pinned in tests/test_sharded.py."""
    b, kvh, g, d = q.shape
    acc, m, l = _launch(
        _partials_kernel, "decode_attention_partials",
        [_head_block(kvh, g, d), _head_block(kvh, g, 1),
         _head_block(kvh, g, 1)],
        [jax.ShapeDtypeStruct((b, kvh, g, d), jnp.float32),
         jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
         jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32)],
        q, k_pool, v_pool, block_tab, k_scale, v_scale, cur_pos,
        interpret=interpret, kv_bits=kv_bits)
    return acc, m[..., 0], l[..., 0]


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret", "kv_bits"))
def decode_attention_partials(
    q: jax.Array,        # (B, KV, G, D)
    k_cache: jax.Array,  # (B, S_local, KV, D) — ONE shard's cache slice
    v_cache: jax.Array,
    k_scale: jax.Array,  # (KV,) f32
    v_scale: jax.Array,
    cur_pos: jax.Array,  # int32 LOCAL valid-slot count: scalar or (B,)
    *,
    block_s: int = 128,
    interpret: bool = False,
    kv_bits: int = 8,
):
    """Dense entry point for the partials kernel (identity block table
    over the shard-local cache slice — same degenerate-table trick as
    ``decode_attention_int8``)."""
    k_pool, v_pool, tab = _dense_pool(k_cache, v_cache, block_s)
    return decode_attention_partials_tiles(
        q, k_pool, v_pool, tab, k_scale, v_scale, cur_pos,
        interpret=interpret, kv_bits=kv_bits)
