"""int4 packed KV cache (kv_bits=4) and int4 weight matmul.

Acceptance pins for the PR's int4 lane:

  * nibble pack/unpack is lossless for every int4 value and odd lengths;
  * the fused Pallas kernels at ``kv_bits=4`` bit-match the jnp reference
    dequant oracle across dense/ring/paged kernel views (the unpack is
    folded into the dequant epilogue — index maps unchanged);
  * packed nibbles survive paged copy-on-rewind rollback and the
    ``state_dict`` round-trip (pre-int4 snapshots default to 8 bits);
  * mixed precision — int8 weights, int4 KV — passes scheduler
    single-stream parity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import (DenseCache, KVCache as KVCacheBase, PagedCache,
                         RingCache, make_cache, set_table_row)
from repro.cache.base import (_safe_scale, dequantize_kv, kv_levels,
                              quantize_kv)
from repro.configs import get_config
from repro.core import api as A
from repro.core import quant as Q
from repro.core.packing import pack_int4, unpack_int4
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.launch import steps as ST
from repro.launch.scheduler import Request, SlotScheduler
from repro.models import build_model


def _int4_tiles(key, b, s, kv=2, d=8):
    """Random PACKED int4 tiles: d logical values -> d//2 storage bytes."""
    raw = jax.random.randint(key, (b, s, kv, d), -7, 8, jnp.int8)
    return pack_int4(raw, axis=-1), raw


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------


class TestPacking:
    def test_all_sixteen_nibbles_roundtrip(self):
        x = jnp.arange(-8, 8, dtype=jnp.int8)
        p = pack_int4(x)
        assert p.shape == (8,) and p.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(unpack_int4(p)),
                                      np.asarray(x))

    @pytest.mark.parametrize("n", [1, 3, 5, 7, 15, 33])
    def test_odd_lengths_pad_and_slice_back(self, n):
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.integers(-8, 8, size=(n,)), jnp.int8)
        p = pack_int4(x)
        assert p.shape == ((n + 1) // 2,)
        np.testing.assert_array_equal(np.asarray(unpack_int4(p, size=n)),
                                      np.asarray(x))

    def test_other_axis(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-8, 8, size=(6, 5)), jnp.int8)
        p = pack_int4(x, axis=0)
        assert p.shape == (3, 5)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(p, axis=0, size=6)), np.asarray(x))

    def test_nd_cache_shape(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.integers(-8, 8, size=(2, 16, 3, 8)), jnp.int8)
        p = pack_int4(x, axis=-1)
        assert p.shape == (2, 16, 3, 4)
        np.testing.assert_array_equal(np.asarray(unpack_int4(p, axis=-1)),
                                      np.asarray(x))


# ---------------------------------------------------------------------------
# quantize/dequantize + scale floor
# ---------------------------------------------------------------------------


class TestQuantizeKV:
    def test_kv_levels(self):
        assert kv_levels(8) == 127.0 and kv_levels(4) == 7.0
        with pytest.raises(ValueError, match="bits"):
            kv_levels(2)

    def test_int4_error_bounded_by_step(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
        t = jnp.max(jnp.abs(x), axis=(0, 1, 3))          # per-head T
        scale = t / kv_levels(4)
        q = quantize_kv(x, scale, bits=4)
        assert q.shape == (1, 32, 2, 4)                   # packed bytes
        y = dequantize_kv(q, scale, bits=4)
        err = jnp.abs(x - y)
        for h in range(2):
            assert float(jnp.max(err[:, :, h])) <= float(scale[h]) / 2 + 1e-6

    def test_int4_clips_to_seven_levels(self):
        x = jnp.full((1, 1, 1, 2), 100.0, jnp.float32)
        scale = jnp.ones((1,), jnp.float32)
        q = quantize_kv(x, scale, bits=4)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(q, axis=-1)), 7)

    def test_scale_floor_handles_degenerate_calibration(self):
        # PR 6's threshold floor, extended to int4: zero and NaN scales
        # clamp to a positive floor -> quantize/dequantize stay finite
        bad = jnp.asarray([0.0, jnp.nan], jnp.float32)
        safe = _safe_scale(bad)
        assert bool(jnp.all(safe > 0)) and bool(jnp.all(jnp.isfinite(safe)))
        cache = DenseCache.init(1, 8, 2, 8, dtype=jnp.int8, quantized=True,
                                bits=4).with_scales(bad, bad)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8))
        kq, vq = cache.ready(x, x)
        k, v = cache.dequantize(kq, vq)
        assert bool(jnp.all(jnp.isfinite(k))) and bool(
            jnp.all(jnp.isfinite(v)))


# ---------------------------------------------------------------------------
# cache layouts
# ---------------------------------------------------------------------------


class TestInt4Layouts:
    def test_storage_halved_and_odd_head_dim_rejected(self):
        c8 = DenseCache.init(1, 16, 2, 8, dtype=jnp.int8, quantized=True)
        c4 = DenseCache.init(1, 16, 2, 8, dtype=jnp.int8, quantized=True,
                             bits=4)
        assert c8.k.shape[-1] == 8 and c4.k.shape[-1] == 4
        assert c4.bits == 4 and c4.kernel_view().bits == 4
        with pytest.raises(ValueError, match="even head dim"):
            DenseCache.init(1, 16, 2, 7, dtype=jnp.int8, quantized=True,
                            bits=4)
        with pytest.raises(ValueError, match="bits"):
            make_cache(1, 16, n_kv=2, head_dim=8, dtype=jnp.int8,
                       quantized=True, bits=3)

    def test_layouts_dequantize_identically(self):
        """The same float K/V readied+appended into dense, ring (window ==
        capacity) and paged int4 caches dequantize to the same tensors."""
        b, s, kv, d = 2, 32, 2, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, kv, d))
        scale = jnp.max(jnp.abs(x), axis=(0, 1, 3)) / kv_levels(4)
        outs = {}
        for name, cache in (
            ("dense", DenseCache.init(b, s, kv, d, dtype=jnp.int8,
                                      quantized=True, bits=4)),
            ("ring", RingCache.init(b, s, kv, d, dtype=jnp.int8,
                                    quantized=True, bits=4)),
            ("paged", PagedCache.init(b, s, kv, d, dtype=jnp.int8,
                                      quantized=True, bits=4, page_size=8)),
        ):
            cache = cache.with_scales(scale, scale)
            kq, vq = cache.ready(x, x)
            cache = cache.append(kq, vq, 0)
            outs[name] = cache.dequantize(*cache.dense_view())
        for name in ("ring", "paged"):
            np.testing.assert_array_equal(np.asarray(outs["dense"][0]),
                                          np.asarray(outs[name][0]))
            np.testing.assert_array_equal(np.asarray(outs["dense"][1]),
                                          np.asarray(outs[name][1]))

    def test_state_dict_roundtrip_preserves_nibbles(self):
        cache = PagedCache.init(1, 32, 2, 8, dtype=jnp.int8, quantized=True,
                                bits=4, page_size=8)
        kq, raw = _int4_tiles(jax.random.PRNGKey(0), 1, 32)
        cache = cache.append(kq, kq, 0)
        back = PagedCache.from_state_dict(cache.state_dict())
        assert back.bits == 4
        np.testing.assert_array_equal(np.asarray(back.k),
                                      np.asarray(cache.k))
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(back.dense_view()[0], axis=-1)),
            np.asarray(raw))

    def test_state_dict_backcompat_defaults_to_int8(self):
        cache = DenseCache.init(1, 8, 2, 8, dtype=jnp.int8, quantized=True)
        sd = cache.state_dict()
        del sd["static"]["bits"]          # pre-int4 snapshot shape
        back = DenseCache.from_state_dict(sd)
        assert back.bits == 8


class TestInt4Rollback:
    def test_paged_copy_on_rewind_preserves_packed_nibbles(self):
        """Speculative rollback into a shared page at bits=4: the copied
        boundary block must carry the packed bytes verbatim (the rewind
        operates on storage bytes, never on logical elements)."""
        ps, nb, d = 8, 4, 8
        cache = PagedCache.init(1, nb * ps, 1, d, dtype=jnp.int8,
                                quantized=True, bits=4, page_size=ps,
                                extra_pages=2)
        fill = (jnp.arange(cache.k.size, dtype=jnp.int32) % 101 - 50
                ).astype(jnp.int8)
        cache = dataclasses.replace(cache, k=fill.reshape(cache.k.shape),
                                    v=(-fill).reshape(cache.v.shape))
        assert cache.k.shape[-1] == d // 2
        shared_page = nb
        private = jnp.arange(nb, dtype=jnp.int32)[None]
        cache = set_table_row(cache, 0,
                              private.at[0, 0].set(shared_page)[0])
        shared_k = np.asarray(cache.k[shared_page]).copy()
        rolled = cache.rollback(jnp.asarray([2], jnp.int32),
                                private_row=private)
        np.testing.assert_array_equal(np.asarray(rolled.k[shared_page]),
                                      shared_k)
        np.testing.assert_array_equal(np.asarray(rolled.k[0]), shared_k)
        # append after the rewind lands in private storage, packed
        kq, raw = _int4_tiles(jax.random.PRNGKey(7), 1, 2, kv=1, d=d)
        after = rolled.append_slots(kq, kq, jnp.asarray([2], jnp.int32))
        np.testing.assert_array_equal(np.asarray(after.k[shared_page]),
                                      shared_k)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(after.k[0, 2:4], axis=-1)),
            np.asarray(raw[0]))


# ---------------------------------------------------------------------------
# kernel parity vs the jnp dequant oracle
# ---------------------------------------------------------------------------


def _scales(rng, kv):
    return jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.1 + 0.05,
                       jnp.float32)


class TestInt4KernelParity:
    @pytest.mark.parametrize("pos", [1, 17, 40])
    def test_decode_matches_oracle(self, pos):
        rng = np.random.default_rng(0)
        b, s, kv, g, d = 2, 40, 3, 4, 16
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k, _ = _int4_tiles(jax.random.PRNGKey(1), b, s, kv, d)
        v, _ = _int4_tiles(jax.random.PRNGKey(2), b, s, kv, d)
        ks, vs = _scales(rng, kv), _scales(rng, kv)
        got = ops.decode_attention(q, k, v, ks, vs, jnp.int32(pos),
                                   block_s=16, kv_bits=4)
        want = kref.decode_attention_ref(q, k, v, ks, vs, pos, kv_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_oracle_equals_unpacked_int8_oracle(self):
        """The int4 oracle IS the int8 oracle on pre-unpacked tiles —
        pins that the only difference is the nibble unpack."""
        rng = np.random.default_rng(3)
        b, s, kv, g, d = 1, 32, 2, 2, 8
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k, kraw = _int4_tiles(jax.random.PRNGKey(3), b, s, kv, d)
        v, vraw = _int4_tiles(jax.random.PRNGKey(4), b, s, kv, d)
        ks, vs = _scales(rng, kv), _scales(rng, kv)
        a = kref.decode_attention_ref(q, k, v, ks, vs, 17, kv_bits=4)
        b_ = kref.decode_attention_ref(q, kraw, vraw, ks, vs, 17)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_prefill_matches_oracle(self):
        rng = np.random.default_rng(1)
        b, sq, sk, kv, g, d = 2, 32, 32, 2, 2, 16
        q = jnp.asarray(rng.normal(size=(b, sq, kv, g, d)), jnp.float32)
        k, _ = _int4_tiles(jax.random.PRNGKey(5), b, sk, kv, d)
        v, _ = _int4_tiles(jax.random.PRNGKey(6), b, sk, kv, d)
        ks, vs = _scales(rng, kv), _scales(rng, kv)
        kl = jnp.asarray([32, 20], jnp.int32)
        got = ops.prefill_attention(q, k, v, ks, vs, jnp.int32(0), kl,
                                    kv_bits=4)
        want = kref.prefill_attention_ref(q, k, v, ks, vs, 0, kl, kv_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-2)

    def test_kernel_views_dense_ring_paged_match(self):
        """One q against the SAME logical contents through all three
        layouts' kernel views: identical decode output (dense/ring use
        the identity table, paged the real one)."""
        b, s, kv, g, d = 1, 128, 2, 2, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, kv, d))
        scale = jnp.max(jnp.abs(x), axis=(0, 1, 3)) / kv_levels(4)
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        outs = {}
        for name, cache in (
            ("dense", DenseCache.init(b, s, kv, d, dtype=jnp.int8,
                                      quantized=True, bits=4)),
            ("ring", RingCache.init(b, s, kv, d, dtype=jnp.int8,
                                    quantized=True, bits=4)),
            ("paged", PagedCache.init(b, s, kv, d, dtype=jnp.int8,
                                      quantized=True, bits=4,
                                      page_size=128)),
        ):
            cache = cache.with_scales(scale, scale)
            cache = cache.append(*cache.ready(x, x), 0)
            view = cache.kernel_view()
            assert view.bits == 4
            ksc, vsc = cache.scales()
            outs[name] = np.asarray(ops.decode_attention_view(
                q, view, ksc, vsc, jnp.int32(100)))
        np.testing.assert_array_equal(outs["dense"], outs["ring"])
        np.testing.assert_array_equal(outs["dense"], outs["paged"])
        # and the fused output matches the oracle on the dense tiles
        cache = DenseCache.init(b, s, kv, d, dtype=jnp.int8, quantized=True,
                                bits=4).with_scales(scale, scale)
        cache = cache.append(*cache.ready(x, x), 0)
        want = kref.decode_attention_ref(q, cache.k, cache.v,
                                         *cache.scales(), 100, kv_bits=4)
        np.testing.assert_allclose(outs["dense"], np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_quant_matmul_int4_weights_match_oracle(self):
        rng = np.random.default_rng(5)
        m, k, n = 16, 256, 16
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w_raw = jnp.asarray(rng.integers(-7, 8, size=(k, n)), jnp.int8)
        w_q = pack_int4(w_raw, axis=0)          # (k//2, n) packed bytes
        w_scale = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.01 + 0.005,
                              jnp.float32)
        act_scale = jnp.float32(127.0 / 3.0)
        got = ops.quant_matmul(x, w_q, w_scale, act_scale, w_bits=4,
                               block_m=16, block_n=16, block_k=128,
                               out_dtype=jnp.float32)
        want = kref.quant_matmul_ref(x, w_q, w_scale, act_scale,
                                     out_dtype=jnp.float32, w_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # oracle parity against the hand-unpacked int8 path
        want8 = kref.quant_matmul_ref(x, w_raw, w_scale, act_scale,
                                      out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(want), np.asarray(want8),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# mixed precision end-to-end: int8 weights, int4 KV
# ---------------------------------------------------------------------------


B, S, GEN = 2, 32, 6
CHUNK = 8


class TestMixedPrecisionParity:
    def test_scheduler_matches_single_stream_int4_kv(self):
        """int8 weights + int4 KV: every request through the continuous-
        batching scheduler generates the same tokens as the single-stream
        pipeline at batch 1."""
        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab)
        policy = A.QuantPolicy(kv_int8=True, kv_bits=4)
        from repro.launch.engine import prepare_int8
        serve_params, qp = prepare_int8(model, cfg, policy, params,
                                        [{"tokens": toks}])

        def single(prompt, n_gen):
            t = np.zeros((1, -(-len(prompt) // CHUNK) * CHUNK), np.int32)
            t[0, :len(prompt)] = prompt
            pre = jax.jit(ST.make_prefill_step(model, cfg, policy,
                                               mode="int8",
                                               prefill_chunk=CHUNK))
            loop = jax.jit(ST.make_decode_loop(model, cfg, policy,
                                               mode="int8", n_steps=n_gen))
            cache = model.init_cache(1, S + GEN + 2, cfg.dtype,
                                     kv_int8=True, kv_bits=4)
            assert all(c.bits == 4 for c in jax.tree.leaves(
                cache, is_leaf=lambda x: isinstance(x, KVCacheBase)))
            lg, cache = pre(serve_params, qp, {"tokens": jnp.asarray(t)},
                            cache, jnp.asarray([len(prompt)], jnp.int32))
            tok0 = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            out, _ = loop(serve_params, qp, tok0, cache, len(prompt))
            return np.asarray(out)[0].tolist()

        lengths = [32, 20, 9]
        reqs = [Request(rid=r, tokens=np.asarray(toks[r % B, :n]),
                        max_gen=GEN) for r, n in enumerate(lengths)]
        sched = SlotScheduler(model, cfg, policy, serve_params, qp,
                              mode="int8", max_slots=2, prompt_cap=S,
                              gen_cap=GEN + 2, prefill_chunk=CHUNK,
                              block_steps=3)
        done = {c.rid: c for c in sched.run(reqs)}
        assert len(done) == len(reqs)
        # the scheduler's batch cache really stores packed int4 tiles
        leaves = jax.tree.leaves(
            sched._cache, is_leaf=lambda x: isinstance(x, KVCacheBase))
        assert leaves and all(c.bits == 4 for c in leaves)
        for r, n in enumerate(lengths):
            want = single(np.asarray(toks[r % B, :n]).tolist(), GEN)
            assert list(done[r].tokens) == want, (r, done[r].tokens, want)
