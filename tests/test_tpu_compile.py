"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode runs a kernel body as Python and cannot see what Mosaic
refuses (block shapes that are neither (8, 128)-aligned nor the whole
array, VMEM over-use).  These tests hand the real kernels, with
``interpret=False``, to the TPU compiler for a v5e chip that is described
rather than attached, at the widths of smollm-135m (d_model 576, 3 KV
heads of 64, d_ff 1536, vocab 49152), and the Mellum2 kernels at its
widths (d_model 2304, 64 experts of width 896, 4 KV heads of 128, a
1024-key window).  Nothing runs: a pass means the
chip's compiler accepts the kernel, not that its results are right (the
interpret-mode parity tests cover that).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers each import
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as DA
from repro.kernels import expert_matmul as EM
from repro.kernels import prefill_attention as PA
from repro.kernels import quant_matmul as QM

KV, G, D = 3, 3, 64          # smollm-135m: 9 query heads over 3 KV heads
S = 1024                     # decode cache length
PAGE = 64                    # paged-layout page size (Engine default)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the kernel count."""
    args = [_spec(sharding, s, d) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return hlo.count("tpu_custom_call")


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(576, 576), (576, 192), (576, 1536),
                                 (1536, 576), (576, 49152)])
def test_quant_matmul_compiles(one_chip, m, k, n):
    def f(x, w, ws, a):
        return QM.quant_matmul(x, w, ws, a, interpret=False)

    assert _compile(f, one_chip, ((m, k), jnp.bfloat16), ((k, n), jnp.int8),
                    ((n,), jnp.float32), ((), jnp.float32)) >= 1


@pytest.mark.parametrize("kv_bits", [8, 4])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_attention_compiles(one_chip, layout, kv_bits):
    b, dp = 4, D * kv_bits // 8
    scalars = [((KV,), jnp.float32), ((KV,), jnp.float32), ((b,), jnp.int32)]
    q = ((b, KV, G, D), jnp.float32)
    if layout == "dense":
        def f(q, k, v, ks, vs, pos):
            return DA.decode_attention_int8(q, k, v, ks, vs, pos,
                                            interpret=False, kv_bits=kv_bits)

        kv = ((b, S, KV, dp), jnp.int8)
        shapes = [q, kv, kv, *scalars]
    else:
        def f(q, k, v, tab, ks, vs, pos):
            return DA.decode_attention_tiles(q, k, v, tab, ks, vs, pos,
                                             interpret=False,
                                             kv_bits=kv_bits)

        kv = ((b * S // PAGE, PAGE, KV, dp), jnp.int8)
        shapes = [q, kv, kv, ((b, S // PAGE), jnp.int32), *scalars]
    assert _compile(f, one_chip, *shapes) >= 1


def test_decode_attention_partials_compiles(one_chip):
    """The sequence-parallel shard kernel over a quarter of the cache."""
    b = 4

    def f(q, k, v, ks, vs, pos):
        return DA.decode_attention_partials(q, k, v, ks, vs, pos,
                                            interpret=False)

    kv = ((b, S // 4, KV, D), jnp.int8)
    assert _compile(f, one_chip, ((b, KV, G, D), jnp.float32), kv, kv,
                    ((KV,), jnp.float32), ((KV,), jnp.float32),
                    ((b,), jnp.int32)) >= 1


@pytest.mark.parametrize("b,sq,sk", [(1, 16, 128), (4, 512, 512)],
                         ids=["admission_chunk", "prompt_512"])
def test_prefill_attention_compiles(one_chip, b, sq, sk):
    def f(q, k, v, ks, vs, qs, kl):
        return PA.prefill_attention_int8(q, k, v, ks, vs, qs, kl,
                                         interpret=False)

    kv = ((b, sk, KV, D), jnp.int8)
    assert _compile(f, one_chip, ((b, sq, KV, G, D), jnp.float32), kv, kv,
                    ((KV,), jnp.float32), ((KV,), jnp.float32),
                    ((), jnp.int32), ((b,), jnp.int32)) >= 1


def test_prefill_attention_paged_compiles(one_chip):
    """A chunked prefill attending a paged pool through its block table."""
    b, sq = 2, 64

    def f(q, k, v, tab, ks, vs, qs, kl):
        return PA.prefill_attention_tiles(q, k, v, tab, ks, vs, qs, kl,
                                          interpret=False)

    kv = ((b * S // PAGE, PAGE, KV, D), jnp.int8)
    assert _compile(f, one_chip, ((b, sq, KV, G, D), jnp.float32), kv, kv,
                    ((b, S // PAGE), jnp.int32), ((KV,), jnp.float32),
                    ((KV,), jnp.float32), ((b,), jnp.int32),
                    ((b,), jnp.int32)) >= 1


@pytest.mark.parametrize("assignments", [512, 128],
                         ids=["decode_64_slots", "admission_chunk"])
@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)],
                         ids=["gate_up", "down"])
def test_expert_matmul_compiles(one_chip, assignments, k, n):
    """Mellum2's grouped expert projections: top-8 assignments of 64 slots
    (a decode step) or of one 16-token chunk, laid out in 16-row tiles
    with room for every routing (``models/moe.py:group_rows``)."""
    e, bm = 64, 16
    tiles = assignments // bm + e

    def f(x, w, ws, sa, te, nl):
        return EM.expert_matmul(x, w, ws, sa, te, nl, block_m=bm)

    assert _compile(f, one_chip, ((tiles * bm, k), jnp.bfloat16),
                    ((e, k, n), jnp.int8), ((e, n), jnp.float32),
                    ((e,), jnp.float32), ((tiles,), jnp.int32),
                    ((), jnp.int32)) >= 1


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_attention_window_compiles(one_chip, layout):
    """A windowed layer's decode at Mellum2's head shape (4 KV heads of 128,
    8 query heads each) over a 768-key cache, window 1024."""
    b, kv, g, d, s, win = 64, 4, 8, 128, 768, 1024
    scalars = [((kv,), jnp.float32), ((kv,), jnp.float32), ((b,), jnp.int32)]
    q = ((b, kv, g, d), jnp.float32)
    if layout == "dense":
        def f(q, k, v, ks, vs, pos):
            return DA.decode_attention_int8(q, k, v, ks, vs, pos,
                                            interpret=False, window=win)

        cache = ((b, s, kv, d), jnp.int8)
        shapes = [q, cache, cache, *scalars]
    else:
        def f(q, k, v, tab, ks, vs, pos):
            return DA.decode_attention_tiles(q, k, v, tab, ks, vs, pos,
                                             interpret=False, window=win)

        cache = ((b * s // PAGE, PAGE, kv, d), jnp.int8)
        shapes = [q, cache, cache, ((b, s // PAGE), jnp.int32), *scalars]
    assert _compile(f, one_chip, *shapes) >= 1
