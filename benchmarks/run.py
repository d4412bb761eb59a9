"""Benchmark harness — one function per paper table/figure.

  table1_scalar_modes   — paper Table 1 (8-bit scalar: symmetric vs
                          asymmetric) on a reduced LM backbone
  table2_vector_modes   — paper Table 2 (8-bit vector modes)
  dws_rescaling         — §3.3/§4.2 sequence: scalar collapse -> rescale
                          recovery -> pointwise fine-tune recovery
  fat_convergence       — §3.2/§4.1.2 training: RMSE distillation loss
                          decreases when training only threshold scales
  kernels_micro         — per-kernel timing (interpret mode on CPU)

Prints ``name,us_per_call,derived`` CSV rows per the harness contract.
Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import api as A
from repro.core import quant as Q
from repro.core.distill import rmse_distill_loss
from repro.models import build_model
from repro.optim.adam import adam_init, adam_update, cosine_restarts

from benchmarks.dws_model import DWSNet


def _timeit(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _agreement(teacher_logits, student_logits):
    """Top-1 agreement — the label-free analog of the paper's top-1
    accuracy (teacher defines the reference prediction)."""
    return float(jnp.mean(
        (jnp.argmax(teacher_logits, -1) == jnp.argmax(student_logits, -1)
         ).astype(jnp.float32)))


def _lm_quant_quality(policy: A.QuantPolicy, batches=4, seed=0):
    """Teacher/student fidelity for one quantization policy on a reduced
    LM backbone (smollm family)."""
    cfg = get_config("smollm-135m", smoke=True).replace(
        n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=384)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    qp = A.init_qparams(model, params, policy)

    def batch_for(i):
        return {"tokens": jax.random.randint(
            jax.random.PRNGKey(100 + i), (8, 64), 0, cfg.vocab)}

    # calibration (paper: ~100 unlabeled samples)
    for i in range(batches):
        ctx = A.make_ctx("calibrate", policy, qp)
        model(params, batch_for(i), ctx)
        for path, obs in ctx.updates.items():
            qp[path] = {**qp[path], "act": obs}
    qp = A.finalize_calibration(qp, policy)

    eval_batch = batch_for(99)
    teacher, _ = model(params, eval_batch)
    student, _ = model(params, eval_batch, A.make_ctx("fake", policy, qp))
    rmse = float(rmse_distill_loss(teacher, student))
    agree = _agreement(teacher, student)
    return rmse, agree


def table1_scalar_modes():
    """Table 1 analog: 8-bit SCALAR quantization, symmetric vs asymmetric.

    Paper finding: asymmetric >= symmetric; scalar mode is the weak
    configuration."""
    rows = []
    for name, sym in (("symmetric", True), ("asymmetric", False)):
        t0 = time.perf_counter()
        rmse, agree = _lm_quant_quality(
            A.QuantPolicy(act_symmetric=sym, weight_per_channel=False))
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"table1_scalar_{name}", us,
                     f"rmse={rmse:.4f};top1_agree={agree:.3f}"))
    return rows


def table2_vector_modes():
    """Table 2 analog: 8-bit VECTOR (per-channel) quantization.

    Paper finding: vector mode is within noise of full precision and
    strictly better than scalar."""
    rows = []
    for name, sym in (("symmetric", True), ("asymmetric", False)):
        t0 = time.perf_counter()
        rmse, agree = _lm_quant_quality(
            A.QuantPolicy(act_symmetric=sym, weight_per_channel=True))
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"table2_vector_{name}", us,
                     f"rmse={rmse:.4f};top1_agree={agree:.3f}"))
    return rows


def dws_rescaling():
    """§3.3 + §4.2 sequence on the planted-outlier DWS net.

    Paper: scalar MobileNet-v2 1.6% -> +rescale 67% -> +pointwise 71%
    (FP 71.55%).  Here: top-1 agreement with the FP model."""
    net = DWSNet()
    key = jax.random.PRNGKey(0)
    params = net.init(key)
    folded = [net.fold_cell(c) for c in params["cells"]]
    x_eval = jax.random.normal(jax.random.PRNGKey(1), (64, 16, net.channels))
    x_cal = jax.random.normal(jax.random.PRNGKey(2), (16, 16, net.channels))

    fp = net.forward_folded(folded, params["head"], x_eval, None)

    rows = []
    t0 = time.perf_counter()
    scalar = net.forward_folded(folded, params["head"], x_eval,
                                {"mode": "scalar"})
    a_scalar = _agreement(fp, scalar)

    rescaled = net.rescale_cells(folded, x_cal)
    resc = net.forward_folded(rescaled, params["head"], x_eval,
                              {"mode": "scalar"})
    a_resc = _agreement(fp, resc)

    vector = net.forward_folded(folded, params["head"], x_eval,
                                {"mode": "vector"})
    a_vector = _agreement(fp, vector)

    # §4.2 pointwise fine-tune on the rescaled scalar model: train
    # per-value scales in [0.75, 1.25] against the FP teacher
    pw = [jnp.ones_like(c["dws_w"]) for c in rescaled]

    def loss_fn(pw):
        cells = [
            {**c, "dws_w": Q.apply_pointwise_scale(c["dws_w"], p)}
            for c, p in zip(rescaled, pw)
        ]
        out = net.forward_folded(cells, params["head"], x_cal,
                                 {"mode": "scalar"})
        ref = net.forward_folded(folded, params["head"], x_cal, None)
        return rmse_distill_loss(ref, out)

    opt = adam_init(pw)
    for step in range(30):
        val, g = jax.value_and_grad(loss_fn)(pw)
        pw, opt = adam_update(g, opt, pw, 2e-2)
    cells_ft = [
        {**c, "dws_w": Q.apply_pointwise_scale(c["dws_w"], p)}
        for c, p in zip(rescaled, pw)
    ]
    ft = net.forward_folded(cells_ft, params["head"], x_eval,
                            {"mode": "scalar"})
    a_ft = _agreement(fp, ft)
    us = (time.perf_counter() - t0) * 1e6

    derived = (f"scalar={a_scalar:.3f};rescaled={a_resc:.3f};"
               f"rescaled_ft={a_ft:.3f};vector={a_vector:.3f}")
    rows.append(("dws_rescaling_sequence", us, derived))
    # paper ordering asserted: collapse < rescaled <= vector-quality
    assert a_scalar < a_resc, (a_scalar, a_resc)
    assert a_vector >= a_scalar, (a_vector, a_scalar)
    return rows


def fat_convergence():
    """§3.2: RMSE between FP and quantized outputs falls when training
    ONLY the threshold scale factors (Adam + cosine annealing)."""
    cfg = get_config("smollm-135m", smoke=True).replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    policy = A.QuantPolicy(weight_per_channel=False)  # stress scalar mode
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (8, 64),
                                          0, cfg.vocab)}
    qp = A.finalize_calibration(_merge_obs(model, params, policy, batch),
                                policy)
    teacher, _ = model(params, batch)

    def loss_fn(qp):
        s, _ = model(params, batch, A.make_ctx("fake", policy, qp))
        return rmse_distill_loss(teacher, s)

    mask = A.trainable_mask(qp)
    opt = adam_init(qp)
    loss0 = float(loss_fn(qp))

    @jax.jit
    def step_fn(qp, opt):
        loss, g = jax.value_and_grad(loss_fn)(qp)
        lr = cosine_restarts(opt.step, 5e-3, 20)
        qp2, opt2 = adam_update(g, opt, qp, lr, mask=mask)
        return qp2, opt2, loss

    t0 = time.perf_counter()
    for i in range(40):
        qp, opt, loss = step_fn(qp, opt)
    us = (time.perf_counter() - t0) / 40 * 1e6
    loss1 = float(loss)
    assert loss1 < loss0, (loss0, loss1)
    return [("fat_convergence_40steps", us,
             f"rmse0={loss0:.4f};rmse40={loss1:.4f};"
             f"improvement={100*(1-loss1/loss0):.1f}%")]


def _merge_obs(model, params, policy, batch):
    qp = A.init_qparams(model, params, policy)
    ctx = A.make_ctx("calibrate", policy, qp)
    model(params, batch, ctx)
    for path, obs in ctx.updates.items():
        qp[path] = {**qp[path], "act": obs}
    return qp


def kernels_micro():
    from repro.kernels import ops, ref

    rows = []
    rng = np.random.default_rng(0)
    m, k, n = 256, 512, 256
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True)
    t_w = Q.max_abs_threshold(w, spec)
    w_q, w_scale = Q.quantize_weights_int8(w, t_w, jnp.ones_like(t_w), spec)
    act_scale = jnp.float32(127.0 / 3.0)
    comb = (w_scale / act_scale).astype(jnp.float32)

    us = _timeit(lambda: ops.quant_matmul(x, w_q, comb, act_scale,
                                          block_m=128, block_n=128,
                                          block_k=256))
    rows.append(("pallas_quant_matmul_interpret", us, f"shape={m}x{k}x{n}"))
    us = _timeit(lambda: ref.quant_matmul_ref(x, w_q, comb, act_scale))
    rows.append(("quant_matmul_ref_xla", us, f"shape={m}x{k}x{n}"))

    t = jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32)) + 0.5
    a = jnp.full((n,), 0.8, jnp.float32)
    xx = jnp.asarray(rng.normal(size=(512, n)), jnp.float32)
    us = _timeit(lambda: ops.fake_quant(xx, t, a))
    rows.append(("pallas_fake_quant_interpret", us, f"shape=512x{n}"))
    us = _timeit(lambda: ref.fake_quant_ref(xx, t, a))
    rows.append(("fake_quant_ref_xla", us, f"shape=512x{n}"))
    return rows


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer repeats (smoke-level timing noise)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    rows = []
    rows += table1_scalar_modes()
    rows += table2_vector_modes()
    rows += dws_rescaling()
    rows += fat_convergence()
    if not args.quick:
        rows += kernels_micro()
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    # paper-ordering checks across tables (vector >= scalar fidelity)
    by = {r[0]: r[2] for r in rows}

    def rmse_of(key):
        return float(by[key].split("rmse=")[1].split(";")[0])

    assert rmse_of("table2_vector_symmetric") <= rmse_of("table1_scalar_symmetric")
    assert rmse_of("table2_vector_asymmetric") <= rmse_of("table1_scalar_asymmetric")
    print("paper_orderings,0,vector<=scalar rmse confirmed")


if __name__ == "__main__":
    main()
