"""Mellum2 (``bench/arch/mellum2.py``) against the program, at a small
Mellum2-shaped size on the CPU: 16 experts top-4, window 8 over
sequences of 40 (so the window binds), and YaRN whose ramp spans dims 1-4
of 8 (``tests/bench/data/tiny-mellum2.json``).

The reference is the harness's own: ``reference_layer`` in float32, a
dense softmax -> top-k -> renormalize over every expert.  The program
runs its serving path as ``SlotScheduler`` drives it: chunked prefill
into a dense cache, then one-token decode steps at per-slot positions
with an active mask.
"""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, hooks, reference, weights

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 7
S, PROMPT, CHUNK = 40, 24, 8

hooks.import_program()
from repro.core import api as A  # noqa: E402
from repro.launch import steps as ST  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import moe as M  # noqa: E402


def config(name="tiny-mellum2"):
    path = (DATA / f"{name}.json" if name.startswith("tiny")
            else ROOT / "bench" / "configs" / f"{name}.json")
    return json.loads(path.read_text())


def ref_logits(cfg, w, toks):
    """The reference's full forward: (S, vocab) float32 logits."""
    mod = arch.of(cfg)
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][toks]
        for lw in f32["layers"]:
            x = mod.reference_layer(cfg, lw, x)
        return reference._rms(x, 1.0, cfg["rms_norm_eps"]) @ f32["embed"].T


def program(cfg, w, dtype=jnp.float32, **over):
    mod = arch.of(cfg)
    pcfg = mod.program_config(cfg).replace(dtype=dtype, **over)
    model = build_model(pcfg)
    hooks.set_norm_eps(model, cfg["rms_norm_eps"])
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype)
                          if a.dtype != jnp.float32 else a,
                          mod.program_params(w))
    return pcfg, model, params


def serve_logits(pcfg, model, params, toks, *, policy, mode, qp=None):
    """Chunked prefill of the first PROMPT tokens into a dense cache, then
    teacher-forced decode steps at per-slot positions: the logits at
    positions PROMPT - 1 .. S - 2 (each predicting the next token)."""
    b = 2   # slot 1 is inactive: its rows route to no expert
    cache = model.init_cache(b, S, pcfg.dtype, kv_int8=policy.kv_int8,
                             layout="dense")
    prefill = jax.jit(ST.make_prefill_step(model, pcfg, policy, mode=mode,
                                           prefill_chunk=CHUNK))
    batch = {"tokens": jnp.tile(toks[None, :PROMPT], (b, 1))}
    lg, cache = prefill(params, qp or {}, batch, cache,
                        jnp.full((b,), PROMPT, jnp.int32))
    out = [lg[0, -1]]
    ctx = ST._serve_ctx(mode, policy, qp or {})
    step = jax.jit(lambda p, t, c, pos: model.decode_step(
        p, t, c, pos, ctx, slot_mask=jnp.asarray([True, False])))
    for i in range(PROMPT, S - 1):
        tok = jnp.asarray([[toks[i]], [0]], jnp.int32)
        lg, cache = step(params, tok, cache, jnp.asarray([i, 0], jnp.int32))
        out.append(lg[0, -1])
    return jnp.stack(out)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def tiny():
    cfg = config()
    w = weights.make(cfg, SEED, dtype=jnp.float32)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], S)
    return cfg, w, toks.astype(np.int32), ref_logits(cfg, w, toks)


def test_float_serving_path_matches_reference(tiny):
    """In float32 and unquantized, the program computes the reference's
    function: 2e-5 relative is float32 rounding through 4 layers (a
    routing or window or rotary mistake moves the logits by tens of
    percent; bfloat16 arithmetic alone by about 1%)."""
    cfg, w, toks, want = tiny
    pcfg, model, params = program(cfg, w)
    got = serve_logits(pcfg, model, params, jnp.asarray(toks),
                       policy=A.QuantPolicy(), mode="none")
    assert rel(got, want[PROMPT - 1:S - 1]) < 2e-5
    # the training forward dispatches by capacity: drop-free at E / k
    _, train, _ = program(cfg, w, capacity_factor=cfg["num_experts"]
                          / cfg["num_experts_per_tok"])
    full, _ = train(params, {"tokens": jnp.asarray(toks)[None]})
    assert rel(full[0], want) < 2e-5


def test_window_and_yarn_bind(tiny):
    """The comparison sees both mechanisms: without the window (every
    layer full) or without YaRN (plain RoPE on the full layer) the
    reference itself moves far past the tolerance above."""
    cfg, w, toks, want = tiny
    no_win = dict(cfg, sliding_window=10**6)
    plain = json.loads(json.dumps(cfg))
    plain["rope_parameters"]["full_attention"] = dict(
        plain["rope_parameters"]["sliding_attention"])
    for other in (no_win, plain):
        assert rel(ref_logits(other, w, toks), want) > 1e-2


def test_int8_serving_path_matches_reference(tiny):
    """The int8 path through the Pallas kernels (interpreted here): int8
    weights with per-expert activation thresholds, int8 KV, the windowed
    decode kernel and ``expert_matmul``.  Its distance to the reference is
    int8 rounding, large at widths of 64 over 8-key windows (0.16
    relative at this seed; bfloat16 alone gives 0.005): 0.3 keeps room
    above it and fails the int4 control (0.73), as bits=4 must."""
    cfg, w, toks, want = tiny
    want = want[PROMPT - 1:S - 1]
    dists = {}
    for bits in (8, 4):
        pcfg, model, params = program(cfg, w, jnp.bfloat16)
        policy = A.QuantPolicy(bits=bits, kv_int8=True, use_pallas=True)
        calib = [{"tokens": jnp.asarray(
            np.random.default_rng(i).integers(0, 256, (4, 32)), jnp.int32)}
            for i in range(2)]
        from repro.launch.engine import prepare_int8
        sp, qp = prepare_int8(model, pcfg, policy, params, calib)
        got = serve_logits(pcfg, model, sp, jnp.asarray(toks), policy=policy,
                           mode="int8", qp=qp)
        dists[bits] = rel(got, want)
    assert dists[8] < 0.3 < dists[4], dists


def test_all_to_one_routing_drops_nothing(tiny):
    """A router that scores every expert alike sends every token to the
    same top-k experts (ties go to the lowest index): each of them gets
    all the tokens, far past the training forward's capacity of ceil(T *
    k / E * 1.25).  The capacity dispatch drops there; the serving path's
    dropless routing, one-shot and chunked, still agrees with the
    reference."""
    cfg, w, toks, _ = tiny
    w = jax.tree.map(lambda a: a, w)
    w["layers"] = [dict(lw, router=jnp.zeros_like(lw["router"]))
                   for lw in w["layers"]]
    want = ref_logits(cfg, w, toks)
    pcfg, model, params = program(cfg, w)
    prefill = ST.make_prefill_step(model, pcfg, A.QuantPolicy(), mode="none")

    def one_shot(p, t):
        cache = model.init_cache(1, S, pcfg.dtype, layout="dense")
        with M.routing_stats() as sink:
            lg, _ = prefill(p, {}, {"tokens": t}, cache)
        return lg, sink

    full, sink = jax.jit(one_shot)(params, jnp.asarray(toks)[None])
    got = serve_logits(pcfg, model, params, jnp.asarray(toks),
                       policy=A.QuantPolicy(), mode="none")
    assert rel(full[0], want[-1:]) < 2e-5
    assert rel(got, want[PROMPT - 1:S - 1]) < 2e-5
    k, e = cfg["num_experts_per_tok"], cfg["num_experts"]
    assert sink and all(int(s["expert_rows_max"]) == S for s in sink)
    assert all(int(s["experts_live"]) == k for s in sink)
    assert S > -(-S * k // e * 5 // 4)
    trained, _ = jax.jit(model)(params, {"tokens": jnp.asarray(toks)[None]})
    assert rel(trained[0], want) > 0.1


# -- the architecture module's contract, bit for bit ----------------------

def test_layer_params_one_layer_alone():
    """A layer made alone equals the same layer of the whole set, bit for
    bit, and carries its kind: the fourth layer is the full one."""
    cfg = config()
    w = weights.make(cfg, 3)
    mod = arch.of(cfg)
    key = weights.root_key(3)
    for i in (0, 3):
        one = mod.layer_params(cfg, key, i, jnp.bfloat16)
        for name, leaf in one.items():
            assert hashlib.sha256(np.asarray(leaf).tobytes()).digest() == \
                hashlib.sha256(np.asarray(w["layers"][i][name]).tobytes()
                               ).digest(), name
    assert [float(lw["is_global"]) for lw in w["layers"]] == [0, 0, 0, 1]


def test_program_gets_the_same_values():
    """The program's tree holds the harness's weights: the experts as
    made, the router as float32, the readout as the embedding, and no
    layer-kind leaf."""
    cfg = config()
    w = weights.make(cfg, 3)
    mod = arch.of(cfg)
    p = mod.program_params(w)
    model = build_model(mod.program_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(p)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(p)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    np.testing.assert_array_equal(np.asarray(p["lm_head"]["w"]),
                                  np.asarray(w["embed"]).T)
    f = p["stack"]["layer3"]["ffn"]
    np.testing.assert_array_equal(np.asarray(f["gate"]["w"]),
                                  np.asarray(w["layers"][3]["w_gate"]))
    assert f["router"]["w"].dtype == jnp.float32
    pcfg = mod.program_config(cfg)
    assert [pcfg.attn_window(i) for i in range(4)] == [8, 8, 8, None]
    assert [pcfg.rope_yarn(i) is not None for i in range(4)] == \
        [False, False, False, True]


def test_reference_yarn_is_the_programs():
    """The reference's YaRN frequencies (its own arithmetic) equal the
    program's, and the ramp binds at both sizes."""
    from repro.models.layers import yarn_inv_freq

    for name in ("tiny-mellum2", "mellum2-12b"):
        cfg = config(name)
        ref, scale = arch.of(cfg).inv_freq(cfg, "full_attention")
        pcfg = arch.of(cfg).program_config(cfg)
        got = yarn_inv_freq(pcfg.head_dim, pcfg.rope_base, pcfg.global_yarn)
        np.testing.assert_allclose(got, ref, rtol=1e-6)
        assert scale == pcfg.global_yarn.attention_factor
        plain, _ = arch.of(cfg).inv_freq(cfg, "sliding_attention")
        moved = ~np.isclose(ref, plain)
        assert 0 < moved.sum() < len(ref)


MODEL_OPS = {
    # per layer: attention 64*(4+2*2)*16 + 4*16*64, router 64*16, four
    # experts 4*3*64*32; 2 operations a weight, 4 layers
    "tiny-mellum2": {"matmul": 303104, "readout": 32768, "per_key": 1024},
    # 2304*(32+8)*128 + 4096*2304 + 2304*64 + 8*3*2304*896, 8 layers
    "mellum2-12b": {"matmul": 1134821376, "readout": 452984832,
                    "per_key": 131072},
}


@pytest.mark.parametrize("name", sorted(MODEL_OPS))
def test_op_counts(name):
    cfg = config(name)
    mod = arch.of(cfg)
    assert mod.model_ops(cfg) == MODEL_OPS[name]
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    assert mod.projections(cfg) == [(d, h * hd), (d, kv * hd),
                                    (d, kv * hd), (h * hd, d), (d, v // n)]
    assert mod.norm_count(cfg) == 2 * cfg["num_hidden_layers"] + 1
