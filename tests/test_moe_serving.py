"""Dropless MoE, windowed decode and YaRN on the serving path.

The grouped int8 expert kernel against its jnp grouping, the decode
kernel's window against its oracle, YaRN's frequencies against
transformers, and the slot scheduler serving a windowed MoE stack
(mellum2's smoke shape) on the dense and paged caches with its routing
counters on every decode block.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api as A
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention_int8
from repro.launch import telemetry as tel
from repro.launch.engine import Engine, prepare_int8
from repro.launch.scheduler import Request
from repro.models import build_model
from repro.models import moe as M
from repro.models.layers import yarn_inv_freq


# -- expert_matmul ------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_expert_matmul_matches_jnp_grouping(bits):
    """Empty experts, a group of one row, one spanning three tiles, and
    masked assignments (routed nowhere): the interpreted kernel equals the
    jnp grouping bit for bit (both accumulate in int32 and dequantize
    with the same f32 products)."""
    e, k, n, bm = 6, 64, 256, 16
    expert = np.array([1] * 40 + [3] + [4] * 9 + [6] * 5, np.int32)
    np.random.default_rng(0).shuffle(expert)
    groups, row_of, counts = M.group_rows(jnp.asarray(expert), e, bm)
    assert counts.tolist() == [0, 40, 0, 1, 9, 0]
    assert int(groups.n_live) == 3 + 1 + 1
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(len(groups.row_valid), k)), jnp.bfloat16)
    x = jnp.where(groups.row_valid[:, None], x, 0)
    w = jnp.asarray(rng.integers(-127, 128, (e, k, n)), jnp.int8)
    ws = jnp.asarray(rng.uniform(1e-3, 2e-3, (e, n)), jnp.float32)
    sa = jnp.asarray(rng.uniform(20, 60, (e,)), jnp.float32)
    got = kops.expert_matmul(x, w, ws, sa, groups.tile_expert, groups.n_live,
                             block_m=bm, bits=bits)
    want = kref.expert_matmul_ref(x, w, ws, sa, groups.row_expert,
                                  groups.sizes, bits=bits)
    live = np.asarray(row_of[row_of < len(groups.row_valid)])
    np.testing.assert_array_equal(np.asarray(got)[live],
                                  np.asarray(want)[live])
    # each assigned row lands in its own expert's group
    assert (np.asarray(groups.row_expert)[live]
            == expert[expert < e]).all()


# -- the training forward's capacity layout ---------------------------------

@pytest.mark.parametrize("mode", ["fake", "int8"])
def test_capacity_layout_matches_dropless(mode):
    """At a drop-free capacity (E / k slots a token) the training
    forward's (G, E, C, d) layout quantizes each expert's activations at
    that expert's threshold, as the dropless grouping of the serving path
    does: the full forward's last logits equal the prefill's (in the
    model's bfloat16, where both round each expert's output alike)."""
    cfg = get_config("mellum2-12b", smoke=True)
    cfg = cfg.replace(capacity_factor=cfg.n_experts / cfg.top_k)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    policy = A.QuantPolicy()
    calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 24), 0,
                                           cfg.vocab)} for i in range(2)]
    sp, qp = prepare_int8(model, cfg, policy, params, calib)
    p = sp if mode == "int8" else params
    ctx = A.make_ctx(mode, policy, qp)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0, cfg.vocab)
    full, _ = model(p, {"tokens": toks}, ctx)
    cache = model.init_cache(1, 24, cfg.dtype, layout="dense")
    last, _ = model.prefill(p, {"tokens": toks}, cache, ctx)
    np.testing.assert_array_equal(np.asarray(full[:, -1]),
                                  np.asarray(last[:, -1]))


# -- decode attention with a window ------------------------------------------

@pytest.mark.parametrize("window", [5, 24])
def test_decode_attention_window_matches_ref(window):
    """Rows at positions 0 (inactive), inside the first tile, and deep in
    the cache, so tiles left of the window are clamped and skipped and the
    edge tile masks: the kernel equals kernels/ref.py to f32 rounding."""
    b, s, kv, g, d = 4, 64, 2, 2, 16
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
    kc = jnp.asarray(rng.integers(-127, 128, (b, s, kv, d)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, (b, s, kv, d)), jnp.int8)
    ks = jnp.asarray([0.01, 0.02], jnp.float32)
    vs = jnp.asarray([0.03, 0.01], jnp.float32)
    pos = jnp.asarray([0, 6, 37, 64], jnp.int32)
    got = decode_attention_int8(q, kc, vc, ks, vs, pos, block_s=8,
                                window=window, interpret=True)
    want = kref.decode_attention_ref(q, kc, vc, ks, vs, pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    full = kref.decode_attention_ref(q, kc, vc, ks, vs, pos)
    assert not np.allclose(np.asarray(full)[2:], np.asarray(want)[2:])


# -- YaRN ---------------------------------------------------------------------

def test_yarn_frequencies_match_transformers():
    """mellum2's global-layer YaRN against transformers'
    ``_compute_yarn_parameters`` (``truncate`` true) where it imports, else
    against the formula written out: low/high correction dims, a linear
    ramp, interpolation by the factor; 1e-6 relative is float32."""
    cfg = get_config("mellum2-12b")
    y, dim, base = cfg.global_yarn, cfg.head_dim, cfg.rope_base
    got = yarn_inv_freq(dim, base, y)
    try:
        from transformers import PretrainedConfig
        from transformers.modeling_rope_utils import _compute_yarn_parameters
    except Exception:
        def at(r):
            return dim * math.log(y.original_max / (r * 2 * math.pi)) / (
                2 * math.log(base))
        low = max(math.floor(at(y.beta_fast)), 0)
        high = min(math.ceil(at(y.beta_slow)), dim - 1)
        extrap = base ** -(np.arange(0, dim, 2) / dim)
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        want = extrap / y.factor * ramp + extrap * (1 - ramp)
        scale = y.attention_factor
    else:
        hf = PretrainedConfig(
            rope_theta=base, hidden_size=cfg.d_model,
            num_attention_heads=cfg.n_heads, head_dim=dim,
            max_position_embeddings=131072,
            rope_scaling={"rope_type": "yarn", "factor": y.factor,
                          "original_max_position_embeddings": y.original_max,
                          "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                          "attention_factor": y.attention_factor,
                          "truncate": True})
        inv, scale = _compute_yarn_parameters(hf, "cpu")
        want = inv.numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert scale == y.attention_factor
    plain = base ** -(np.arange(0, dim, 2) / dim)
    assert 0 < (~np.isclose(got, plain)).sum() < dim // 2
    assert [cfg.rope_yarn(i) is not None for i in range(4)] == \
        [False, False, False, True]


# -- the slot scheduler on a windowed MoE stack -------------------------------

def _engine(layout):
    cfg = get_config("mellum2-12b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    policy = A.QuantPolicy(kv_int8=True)
    calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (4, 32), 0,
                                           cfg.vocab)} for i in range(2)]
    sp, qp = prepare_int8(model, cfg, policy, params, calib)
    return cfg, Engine(model, cfg, policy, sp, qp, mode="int8",
                       cache_layout=layout)


def _serve(eng):
    sched = eng.make_scheduler(max_slots=3, prompt_cap=24, gen_cap=24,
                               block_steps=4)
    emitted = []
    decode0 = sched._decode

    def decode(*args):
        out = decode0(*args)
        emitted.append(int(np.asarray(out[1]).sum()))
        return out

    sched._decode = decode
    rng = np.random.default_rng(3)
    # prompts past the window of 8, answers that carry past it too
    reqs = [Request(rid=i, tokens=rng.integers(0, 256, int(n)).astype(
        np.int32), max_gen=14, arrive_ms=0.0)
        for i, n in enumerate([20, 9, 23, 15])]
    done = sched.run(reqs)
    recs = [r for r in tel.tracer().records(tel.tracer().records()[-1].run_id)
            if r.name == "sched.decode"]
    return {c.rid: list(c.tokens) for c in done}, recs, emitted


def test_scheduler_serves_windows_on_dense_and_paged():
    """Windowed layers run on the dense and the paged cache, where the
    window is a mask, and the two serve the same tokens; every decode
    block records its routing counters: each emitted token's step routed
    top_k rows in each layer."""
    cfg, dense = _engine("dense")
    toks_d, recs, emitted = _serve(dense)
    assert len(toks_d) == 4 and all(len(t) == 14 for t in toks_d.values())
    rows = sum(r.attrs["expert_rows"] for r in recs)
    assert rows == cfg.top_k * cfg.n_layers * sum(emitted)
    for r in recs:
        a = r.attrs
        assert a["expert_rows_max"] <= 3 * 4 * cfg.n_layers
        assert 0 < a["experts_live"] <= min(a["expert_rows"],
                                            cfg.n_experts * 4 * cfg.n_layers)
    _, paged = _engine("paged")
    toks_p, _, _ = _serve(paged)
    assert toks_p == toks_d


def test_scheduler_rejects_windows_on_the_ring():
    """The ring layout keeps a window's last positions only, which slots
    at their own positions cannot address (an Engine's scheduler takes the
    dense layout in its place)."""
    from repro.launch.scheduler import SlotScheduler

    cfg, eng = _engine("dense")
    with pytest.raises(ValueError, match="ring layout"):
        SlotScheduler(eng.model, cfg, eng.policy, eng.serve_params,
                      eng.qparams, max_slots=2, prompt_cap=16, gen_cap=8,
                      cache_layout="ring")
