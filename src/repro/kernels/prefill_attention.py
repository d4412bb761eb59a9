"""Pallas TPU kernel: fused int8-KV flash-prefill attention.

The prefill twin of kernels/decode_attention.py: where decode reads the
whole cache for ONE query token, prefill attends a whole prompt (or one
chunk of it) against the int8 KV stream — the dominant HBM cost of long
prompts.  The paper's frozen per-head thresholds (FAT §2, calibrated then
finalized) make the KV scales static at serve time, so the prompt's K/V
quantize once and this kernel attends directly over the int8 tiles the
cache stores — no bf16 re-materialization between "attend" and "append".

    k/v int8 tile --DMA--> VMEM --(scales fold into q / epilogue)--> f32
    s   = (q * k_scale / sqrt(D)) @ k_tile^T            (MXU)
    m,l = running max / normalizer update               (VPU)
    acc = acc * exp(m_old - m_new) + softmax_tile @ v_tile
    out = acc * v_scale / l                             (epilogue)

Tiles and block tables
----------------------
Like the decode kernel, the core (``prefill_attention_tiles``) reads KV
through a **block table**: tiles arrive as a page pool
``(pages, block_k, KV, D)`` and a ``(B, KV-chunks)`` int32 table maps
each (batch row, logical KV block) to a pool page via a scalar-prefetch
index map.  The paged cache passes its pool/table straight through (so a
chunked prefill can attend pages shared with other requests); the dense
entry point ``prefill_attention_int8`` reshapes its contiguous stream
and passes the identity table — one kernel body for every layout.

Grid layout
-----------
``(B, Q-chunks, KV-chunks)`` with the KV axis innermost and declared
"arbitrary" (the two outer axes are "parallel"): in-order execution
along KV is what lets the per-head (block_q * G, D) accumulator tiles
live in VMEM scratch across KV steps — classic flash-attention online
softmax.  Each step DMAs one ``(1, block_k, KV, D)`` tile holding every
KV head and loops over the heads in the body (static unroll), as the
decode kernel does and for the same reason: Mosaic refuses a one-head
``(1, block_k, 1, D)`` block over the ``(pages, block_k, KV, D)`` pool.
The (KV,) scales sit whole in SMEM.  GQA groups are flattened into the
query-row axis so every kernel tile is a plain 2D matmul operand.

VMEM scratch expectations
-------------------------
Three scratch buffers persist across the innermost (KV) axis: the
(KV, block_q * G, D) f32 output accumulator plus (KV, block_q * G, 1)
running max and normalizer.  They are (re)initialized at ``ki == 0`` and
flushed at ``ki == n_k - 1``, so correctness relies on the KV axis
running in-order on one core (the "arbitrary" dimension contract).  Per
step the resident set adds one (block_k, KV, D) int8 K tile and V tile;
the default 256-row blocks fit VMEM at the served configs' head counts
and dims (paged pools use their page size as block_k).

Masking semantics
-----------------
Positional and block-skipped, always in LOGICAL positions (block index *
block_k + offset — the table only relocates storage): causal and
sliding-window predicates are evaluated per TILE first and a fully-masked
tile skips its matmuls entirely via ``pl.when`` — a sliding-window layer
therefore costs O(S * window) compute, not O(S^2).  ``q_start`` (chunk
offset of query row 0 — a scalar, or a (B,) vector for the per-slot
speculative-verify pass) and ``kv_len`` (per-request valid KV count)
make the same executable serve chunked, ragged prefill: element masks
re-apply after the running-max update (an all-masked tile has
s == m_new == NEG_INF and exp(0) == 1), and padded/garbage rows end
with l == 0, normalizing to exact zeros like the decode kernel's
empty-cache case.  The decode kernel's per-slot ``cur_pos`` vector and
the slot scheduler's inactive slots (kv_len == 0) reuse this same
convention.

DMA skip (index-map clamp)
--------------------------
``q_start`` / ``kv_len`` ride the scalar-prefetch path next to the block
table, so the K/V index maps can see them: a KV block whose every (q, k)
pair is masked (beyond the causal frontier, past ``kv_len``, or left of
the sliding-window band) has its block index CLAMPED to the nearest live
block — Pallas elides the copy when a block's index repeats between grid
steps, so fully-dead tiles cost neither MXU time (``pl.when``, as
before) nor DMA bandwidth.  The clamp predicate is exactly the kernel
body's ``live`` predicate, so a clamped tile is never read.
``dma_skip=False`` keeps the unclamped maps (the parity oracle in
tests/test_prefill_fastpath.py).  The decode kernel clamps and skips the
same way from its per-row ``cur_pos``, always.

A bf16/f32 K/V stream runs through the same kernel with scales == 1.
The pure-jnp oracle is kernels/ref.py::prefill_attention_ref.
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
# one bf16 pass, the output is 4.4e-3 of max |output| off the oracle on
# a v5e, with these passes 5e-7.  The extra passes' time is not measured
_F32 = jax.lax.Precision.HIGHEST


def _kernel(tab_ref, qs_ref, kl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            o_ref, acc_ref, m_ref, l_ref, *, n_k: int, block_q: int,
            block_k: int, groups: int, dim: int, causal: bool,
            window: int | None, kv_bits: int):
    # tab_ref: scalar-prefetch block table — consumed by the K/V index
    # maps only; positions below are logical.  qs_ref/kl_ref are the
    # per-request (B,) q_start / kv_len vectors, shared with the index
    # maps (the DMA-skip clamp) and read per batch row here.
    del tab_ref
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qs_ref[bi]
    kv_len = kl_ref[bi]
    q_lo = q_start + qi * block_q      # absolute position of query row 0
    k_lo = ki * block_k                # absolute position of key col 0

    # block-level skip: a tile whose every (q, k) pair is masked never
    # touches the MXU — causal skips the upper-triangular half, a sliding
    # window additionally skips everything left of the band
    live = k_lo < kv_len
    if causal:
        live &= k_lo <= q_lo + (block_q - 1)
    if window is not None:
        live &= k_lo + (block_k - 1) >= q_lo - (window - 1)

    @pl.when(live)
    def _tile():
        # element masks: GQA groups are flattened into rows, so row r is
        # query position q_lo + r // G
        rows = block_q * groups
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // groups
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            valid &= k_pos <= q_pos
        if window is not None:
            valid &= (q_pos - k_pos) < window
        inv_sqrt_d = jax.lax.rsqrt(jnp.asarray(dim, jnp.float32))

        for h in range(q_ref.shape[1]):
            # fold key dequant scale and 1/sqrt(D) into q: the scale is
            # uniform within a head, so (q*c) @ k_int8 == c * (q @ k)
            q = q_ref[0, h].astype(jnp.float32) * (ks_ref[h] * inv_sqrt_d)
            k = k_ref[0, :, h, :]                        # (block_k, D)
            if kv_bits == 4:
                # int4 lane: one nibble unpack in VMEM before the f32
                # cast the int8 path already pays; scales carry T/7, so
                # the q-fold above is unchanged
                k = unpack_int4(k, axis=-1)
            k = k.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )                                            # (rows, block_k)
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[h]                            # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # re-mask: an all-masked row has s == m_new == NEG_INF and
            # exp(0) == 1
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, :, h, :]                        # (block_k, D)
            if kv_bits == 4:
                v = unpack_int4(v, axis=-1)
            v = v.astype(jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )
            m_ref[h] = m_new

    @pl.when(ki == n_k - 1)
    def _epilogue():
        # value dequant folds once into the epilogue (linear in v); rows
        # with no visible key (padding / ragged tail) have l == 0 -> zeros
        for h in range(o_ref.shape[1]):
            o = acc_ref[h] * vs_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = o.astype(o_ref.dtype)


def _fit_block(s: int, target: int) -> int:
    """Largest sublane-aligned tile <= target (padding covers remainders)."""
    return max(8, min(target, -(-s // 8) * 8) // 8 * 8)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "out_dtype",
                     "interpret", "dma_skip", "kv_bits"))
def prefill_attention_tiles(
    q: jax.Array,          # (B, Sq, KV, G, D) float — prompt queries
    k_pool: jax.Array,     # (pages, block_k, KV, D) int8/float (D/2 packed
    v_pool: jax.Array,     # (pages, block_k, KV, D)   bytes at kv_bits=4)
    block_tab: jax.Array,  # (B, KV-chunks) int32 page per logical block
    k_scale: jax.Array,    # (KV,) f32 per-head dequant scale
    v_scale: jax.Array,    # (KV,) f32 per-head dequant scale
    q_start: jax.Array,    # scalar or (B,) int32: position of query row 0
    kv_len: jax.Array,     # (B,) int32: valid KV count per request
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
    dma_skip: bool = True,
    kv_bits: int = 8,
):
    """Kernel core: fused multi-query-row flash attention over
    block-table-mapped KV tiles.  Returns (B, Sq, KV, G, D).

    ``q_start`` may be per-request (B,): the speculative-verify pass runs
    each slot's draft window at its own offset through this one
    executable (a scalar broadcasts — chunked prefill's uniform offset).
    ``dma_skip=False`` disables the masked-tile index-map clamp (see
    module docstring), for parity testing only.  ``kv_bits == 4``: K/V
    tiles hold packed nibbles (D/2 bytes) unpacked in the kernel body;
    index maps (including the DMA-skip clamp) are unchanged — they
    address blocks, not bytes.
    """
    b, sq, kvh, g, d = q.shape
    dp = k_pool.shape[-1]  # storage width (D, or D/2 packed)
    assert dp * (2 if kv_bits == 4 else 1) == d, (
        f"kv_bits={kv_bits}: pool head dim {dp} does not match q head "
        f"dim {d}")
    bk = k_pool.shape[1]
    n_k = block_tab.shape[1]

    bq = _fit_block(sq, block_q)
    sq_p = -(-sq // bq) * bq
    # prefill runs once per prompt (or chunk), so unlike the decode kernel
    # a pad copy here is not on the per-token path — plain jnp.pad is fine
    if sq_p != sq:
        q = jnp.pad(q, [(0, 0), (0, sq_p - sq), (0, 0), (0, 0), (0, 0)])
    n_q = sq_p // bq

    # flatten GQA groups into the query-row axis: (B, KV, Sq*G, D) keeps
    # every kernel tile a 2D matmul operand
    q2 = jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(b, kvh, sq_p * g, d)
    rows = bq * g

    kernel = functools.partial(
        _kernel, n_k=n_k, block_q=bq, block_k=bk, groups=g, dim=d,
        causal=causal, window=window, kv_bits=kv_bits)

    def kv_index(bi, qi, ki, tab, qs, kl):
        if dma_skip:
            # clamp a fully-masked block to the nearest LIVE block: its
            # page index then repeats a neighbouring grid step's, so the
            # copy is elided.  The live range below mirrors the kernel
            # body's ``live`` predicate exactly (see _kernel), so a
            # clamped tile's (wrong) contents are never read.
            q_lo = qs[bi] + qi * bq
            last = jnp.minimum(n_k - 1,
                               (jnp.maximum(kl[bi], 1) - 1) // bk)
            if causal:
                last = jnp.minimum(last, (q_lo + bq - 1) // bk)
            first = 0
            if window is not None:
                first = jnp.maximum(0, (q_lo - (window - 1)) // bk)
            ki = jnp.clip(ki, first, last)
        return (tab[bi, ki], 0, 0, 0)

    q_block = pl.BlockSpec((1, kvh, rows, d),
                           lambda bi, qi, ki, tab, qs, kl: (bi, 0, qi, 0))
    kv_tile = pl.BlockSpec((1, bk, kvh, dp), kv_index)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_q, n_k),
        in_specs=[q_block, kv_tile, kv_tile, smem, smem],
        out_specs=q_block,
        scratch_shapes=_scratch(kvh, rows, d),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, sq_p * g, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="prefill_attention",
    )(
        block_tab.astype(jnp.int32),
        jnp.broadcast_to(jnp.asarray(q_start, jnp.int32).reshape(-1), (b,)),
        jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,)),
        q2,
        k_pool,
        v_pool,
        k_scale.reshape(kvh).astype(jnp.float32),
        v_scale.reshape(kvh).astype(jnp.float32),
    )
    out = out.reshape(b, kvh, sq_p, g, d).transpose(0, 2, 1, 3, 4)
    return out[:, :sq]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "out_dtype",
                     "interpret", "dma_skip", "kv_bits"))
def prefill_attention_int8(
    q: jax.Array,        # (B, Sq, KV, G, D) float — prompt queries, GQA view
    k: jax.Array,        # (B, Sk, KV, D) int8 (or float with scales == 1;
    v: jax.Array,        # (B, Sk, KV, D)  D/2 packed bytes at kv_bits=4)
    k_scale: jax.Array,  # (KV,) f32 per-head dequant scale
    v_scale: jax.Array,  # (KV,) f32 per-head dequant scale
    q_start: jax.Array,  # scalar or (B,) int32: position of query row 0
    kv_len: jax.Array,   # (B,) int32: valid KV count per request
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
    dma_skip: bool = True,
    kv_bits: int = 8,
):
    """Dense entry point: a contiguous (B, Sk, KV, D) KV stream
    degenerates to the identity block table over a free leading-axis
    reshape — same kernel body as the paged layout."""
    b = q.shape[0]
    sk = k.shape[1]
    kvh, d = k.shape[2], k.shape[3]

    bk = _fit_block(sk, block_k)
    sk_p = -(-sk // bk) * bk
    if sk_p != sk:
        pad = [(0, 0), (0, sk_p - sk), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    n_k = sk_p // bk
    k_pool = k.reshape(b * n_k, bk, kvh, d)
    v_pool = v.reshape(b * n_k, bk, kvh, d)
    tab = jnp.arange(b * n_k, dtype=jnp.int32).reshape(b, n_k)
    return prefill_attention_tiles(
        q, k_pool, v_pool, tab, k_scale, v_scale, q_start, kv_len,
        causal=causal, window=window, block_q=block_q, out_dtype=out_dtype,
        interpret=interpret, dma_skip=dma_skip, kv_bits=kv_bits)


def _scratch(kvh, rows, d):
    return [
        pltpu.VMEM((kvh, rows, d), jnp.float32),  # output accumulator
        pltpu.VMEM((kvh, rows, 1), jnp.float32),  # running max
        pltpu.VMEM((kvh, rows, 1), jnp.float32),  # running normalizer
    ]
