"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracle
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.core import quant as Q


def _mk(m, k, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True, channel_axis=-1)
    t_w = Q.max_abs_threshold(w, spec)
    w_q, w_scale = Q.quantize_weights_int8(w, t_w, jnp.ones_like(t_w), spec)
    t_a = jnp.float32(3.0)
    act_scale = 127.0 / t_a
    comb_scale = (w_scale * (1.0 / act_scale)).astype(jnp.float32)
    return x, w_q, comb_scale, act_scale


class TestQuantMatmul:
    @pytest.mark.parametrize("m,k,n", [(8, 16, 8), (32, 64, 16), (128, 256, 128),
                                       (64, 512, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, m, k, n, dtype):
        x, w_q, scale, act_scale = _mk(m, k, n, dtype)
        got = ops.quant_matmul(x, w_q, scale, act_scale,
                               block_m=min(32, m), block_n=min(128, n),
                               block_k=min(128, k), out_dtype=jnp.float32)
        want = kref.quant_matmul_ref(x, w_q, scale, act_scale,
                                     out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_k_step_accumulation(self):
        # K split across 4 grid steps exercises the VMEM accumulator path
        x, w_q, scale, act_scale = _mk(16, 512, 16, jnp.float32, seed=3)
        got = ops.quant_matmul(x, w_q, scale, act_scale,
                               block_m=16, block_n=16, block_k=128,
                               out_dtype=jnp.float32)
        want = kref.quant_matmul_ref(x, w_q, scale, act_scale, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_saturation(self):
        # activations beyond the threshold saturate at ±127 (paper eq. 4)
        x = jnp.full((8, 16), 100.0, jnp.float32)
        w = jnp.ones((16, 8), jnp.float32)
        spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True)
        t_w = Q.max_abs_threshold(w, spec)
        w_q, w_scale = Q.quantize_weights_int8(w, t_w, jnp.ones_like(t_w), spec)
        act_scale = jnp.float32(127.0 / 1.0)  # T_a = 1 << 100
        got = ops.quant_matmul(x, w_q, (w_scale / act_scale), act_scale,
                               block_m=8, block_n=8, block_k=16,
                               out_dtype=jnp.float32)
        # every product is 127 (saturated) * 1 -> sum over K=16: 16 * 127/127 = 16
        np.testing.assert_allclose(np.asarray(got), 16.0, rtol=1e-6)


class TestFakeQuantKernel:
    @pytest.mark.parametrize("m,n", [(8, 8), (64, 128), (256, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, m, n, dtype):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(m, n)) * 2, dtype)
        t = jnp.asarray(np.abs(rng.normal(size=(n,))) + 0.5, jnp.float32)
        a = jnp.asarray(rng.uniform(0.5, 1.0, size=(n,)), jnp.float32)
        got = ops.fake_quant(x, t, a)
        want = kref.fake_quant_ref(x, t, a)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-5, atol=1e-5)

    def test_matches_core_quant(self):
        """Kernel == repro.core.quant.fake_quant_symmetric (vector mode)."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True,
                           channel_axis=-1)
        t = Q.max_abs_threshold(x, spec)
        a = jnp.full((16,), 0.8, jnp.float32)
        got = ops.fake_quant(x, t, a)
        want = Q.fake_quant_symmetric(x, t, a, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_ste_gradients(self):
        """custom_vjp backward: dx is STE-masked, dalpha matches the
        autodiff gradient of the unfused reference."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        t = jnp.asarray(np.abs(rng.normal(size=(8,))) + 1.0, jnp.float32)
        a = jnp.full((8,), 0.8, jnp.float32)

        def f_kernel(x, a):
            return jnp.sum(ops.fake_quant(x, t, a) ** 2)

        spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True,
                           channel_axis=-1)

        def f_ref(x, a):
            return jnp.sum(Q.fake_quant_symmetric(x, t, a, spec) ** 2)

        gx_k, ga_k = jax.grad(f_kernel, argnums=(0, 1))(x, a)
        gx_r, ga_r = jax.grad(f_ref, argnums=(0, 1))(x, a)
        np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ga_k), np.asarray(ga_r),
                                   rtol=1e-4, atol=1e-4)
