#!/usr/bin/env python3
"""Serve smollm-135m at its published widths on a TPU and check the result.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sequence-parallel serving on four

One chip, in one process, through the user-facing entry points
(``Engine.from_checkpoint`` -> ``generate_batch`` / ``generate``):

  (a) device     JAX must see a TPU; anything else exits 1 before any work.
  (b) engine     30 layers, d_model 576, 9 heads / 3 KV heads of 64, d_ff
                 1536, vocab 49152, random init from ``--seed``; int8
                 weights and int8 KV, Pallas kernels compiled (not
                 interpreted).  The compiled prefill and decode must hold
                 the quant_matmul and attention kernels.
  (c) batched    ``generate_batch``: 4 prompts of 128 tokens, 32 tokens each.
  (d) scheduler  ``generate`` through the slot scheduler with ragged
                 requests; every request must end ``ok``.
  (e) agreement  the same params served by the XLA int8 path
                 (``use_pallas=False``): prefill logits within
                 ``LOGIT_TOL`` and each first greedy token in the
                 reference's top ``TOP_K``; then each kernel, at these
                 widths, against its jnp oracle (kernels/ref.py) within
                 ``KERNEL_TOL``.

``--chips 4`` runs only the sharded path: ``ShardedEngine`` with the KV
cache's sequence axis split over four chips (sp=4; tp is left out because
9 heads / 3 KV heads do not divide by 4), against a one-chip engine on
the same params, step by step with the same input tokens (teacher
forcing).  sp=4 decode merges four partial softmaxes, which rounds
differently from the one-chip kernel, so its logits are held two ways:
within ``MERGE_TOL`` of one chip computing that same four-way merge
(``split_merge_decode``), and within ``SP_TOL`` of the one-chip kernel.
Two planted faults (``frontier_slot``, ``dropped_shard``) must fail
those checks in the same run.  Its all-reduces must carry integers, and
its memory must sit on all four devices.

Times printed here are set-up or informational, never device metrics.
The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check exits 1 and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
BATCH, PROMPT, GEN = 4, 128, 32
# Kernel and XLA int8 paths differ at rounding level only, but 210 static
# activation requantizations turn a rounding difference into whole-LSB
# flips, and a random-init 30-layer stack amplifies those: on these
# weights an exactly equivalent re-chunking of the XLA attention alone
# moves the prefill logits by 3-5% (relative L2, centered), the kernel
# path by 7-12% (CPU, interpret mode).  Unrelated logits score ~1.41.
LOGIT_TOL = 0.25   # per-row ||kernels - XLA|| / ||XLA - mean(XLA)||
TOP_K = 5          # first greedy token must be in the XLA path's top 5
# max |kernel - oracle| / max |oracle|, per kernel.  On a v5e the
# attention kernels read 7e-7 with their f32 dot precision and 2.2e-3 /
# 4.4e-3 with Mosaic's one-pass bf16 default, which this limit rejects
KERNEL_TOL = 1e-4
SP = 4
# sp=4 vs one chip running the same four-way partials merge: only the
# mesh differs (owner writes, per-shard valid counts, gathers).  On a v5e
# the two are bit-identical (0.0); the planted faults read 0.096 and 0.68
MERGE_TOL = 1e-3
# sp=4 vs the one-chip kernel, per-row relative L2 at every step.  On a
# v5e the sound reading is 0.049: f32 merge order, amplified by 210
# static requantizations of random weights (the one-chip four-way merge
# reads the same 0.049).  The frontier fault reads 0.0995; this limit
# sits between the two
SP_TOL = 0.07
# (prompt length, generated tokens) of the ragged scheduler requests
SCHED_REQS = [(128, 32), (37, 16), (5, 8), (100, 24), (64, 32), (17, 12)]
SCHED_SLOTS = 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def kernel_counts(compiled) -> Counter:
    """Pallas kernels in a compiled executable, by kernel name."""
    return Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?'
        r'op_name="[^"]*/(\w+)/pallas_call"', compiled.as_text()))


def prompts(cfg, seed: int):
    from repro.configs.shapes import ShapeSpec
    from repro.data import pipeline as DP

    spec = DP.spec_for(cfg, ShapeSpec("chip_smoke", "train", PROMPT, BATCH))
    batch = DP.make_batch(spec, seed)
    batch.pop("labels", None)
    return batch


def device_info(jax, n: int) -> dict:
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX sees {devs[0].platform} devices")
    check(len(devs) >= n, f"need {n} chips, JAX sees {len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}
    log(f"device: {info} (jax {jax.__version__})")
    return info


def forced_logits(engine, batch, tokens=None):
    """(B, 1 + n, V) f32 logits of ``engine``: the prefill's last position,
    then one decode step per column of ``tokens`` (B, n), fed those tokens
    whatever the engine would have picked (teacher forcing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps as ST

    args = (engine.model, engine.cfg, engine.policy, engine.mode)
    prefill = jax.jit(ST.make_prefill_step(*args), donate_argnums=(3,))
    decode = jax.jit(ST.make_serve_step(*args), donate_argnums=(3,))
    cache = engine.init_cache(BATCH, engine._cache_len(PROMPT, GEN))
    logits, cache = prefill(engine.serve_params, engine.qparams, batch, cache)
    out = [logits[:, -1]]
    for i in range(0 if tokens is None else tokens.shape[1]):
        _, logits, cache = decode(engine.serve_params, engine.qparams,
                                  jnp.asarray(tokens[:, i:i + 1]), cache,
                                  jnp.int32(PROMPT + i))
        out.append(logits[:, -1])
    return np.stack([np.asarray(o, np.float32) for o in out], axis=1)


def agreement(got, want):
    """Relative L2 of ``got`` against the centered ``want`` logits, and
    the rank of ``got``'s greedy pick among ``want``'s, per position."""
    import numpy as np

    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want - want.mean(-1, keepdims=True), axis=-1))
    picked = np.take_along_axis(want, got.argmax(-1)[..., None], axis=-1)
    return rel, (want > picked).sum(-1)


def kernel_parity(cfg, seed: int) -> dict:
    """Each kernel against its jnp oracle at this config's widths, on
    random int8 data: max |kernel - oracle| / max |oracle|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    kvh, d, s = cfg.n_kv_heads, cfg.head_dim, 1024
    g = cfg.n_heads // kvh

    def int8(key, shape):
        return jax.random.randint(key, shape, -127, 128, jnp.int32).astype(
            jnp.int8)

    x = jax.random.normal(ks[0], (BATCH * PROMPT, cfg.d_model), jnp.bfloat16)
    w = int8(ks[1], (cfg.d_model, cfg.d_ff))
    w_scale = jnp.full((cfg.d_ff,), 1e-3, jnp.float32)
    a_scale = jnp.float32(40.0)
    k, v = int8(ks[2], (BATCH, s, kvh, d)), int8(ks[3], (BATCH, s, kvh, d))
    kv_scale = jnp.linspace(0.01, 0.03, kvh, dtype=jnp.float32)
    qd = jax.random.normal(ks[4], (BATCH, kvh, g, d), jnp.float32)
    pos = jnp.asarray([s, 700, 129, 1], jnp.int32)[:BATCH]
    qp = jax.random.normal(ks[5], (BATCH, PROMPT, kvh, g, d), jnp.float32)
    kv_len = jnp.full((BATCH,), PROMPT, jnp.int32)
    pairs = {
        "quant_matmul": (
            lambda: ops.quant_matmul(x, w, w_scale, a_scale,
                                     out_dtype=jnp.float32),
            lambda: ops.quant_matmul_ref(x, w, w_scale, a_scale,
                                         out_dtype=jnp.float32)),
        "decode_attention": (
            lambda: ops.decode_attention(qd, k, v, kv_scale, kv_scale, pos),
            lambda: ops.decode_attention_ref(qd, k, v, kv_scale, kv_scale,
                                             pos)),
        "prefill_attention": (
            lambda: ops.prefill_attention(
                qp, k[:, :PROMPT], v[:, :PROMPT], kv_scale, kv_scale,
                jnp.int32(0), kv_len),
            lambda: ops.prefill_attention_ref(
                qp, k[:, :PROMPT], v[:, :PROMPT], kv_scale, kv_scale, 0,
                kv_len)),
    }
    out = {}
    for name, (kern, oracle) in pairs.items():
        got = np.asarray(kern(), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(), np.float32)
        out[name] = float(np.abs(got - want).max() / np.abs(want).max())
    return out


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.launch.engine import Engine
    from repro.launch.scheduler import Request

    info = device_info(jax, 1)

    # (b) engine and kernel path
    t0 = time.time()
    eng = Engine.from_checkpoint(ARCH, smoke=False, init_seed=seed)
    log(f"engine built in {time.time() - t0:.1f} s (set-up: init + "
        f"calibration) | int8 weight tensors {eng.n_int8_weights()}")
    check(not ops._interpret(), "kernels would run in interpret mode")
    check(eng.policy.use_pallas, "use_pallas did not default on for TPU")
    check(eng.policy.kv_int8 and eng.policy.kv_bits == 8, "KV is not int8")
    check(eng.n_int8_weights() > 0, "no int8 weights resident")
    cfg = eng.cfg
    check((cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.head_dim, cfg.d_ff, cfg.vocab) ==
          (30, 576, 9, 3, 64, 1536, 49152), f"not full width: {cfg}")

    # (c) batched serving
    batch = prompts(cfg, seed)
    t0 = time.time()
    res = eng.generate_batch(batch, GEN)
    wall = time.time() - t0
    setup = wall - res.prefill_s - res.decode_s
    log(f"generate_batch {BATCH}x{PROMPT} -> {GEN}: wall {wall:.1f} s, of "
        f"which compile + host set-up {setup:.1f} s; prefill "
        f"{res.prefill_s * 1e3:.1f} ms, decode {res.decode_s * 1e3:.1f} ms "
        "(informational, host clock)")
    toks = np.asarray(res.tokens)
    check(toks.shape == (BATCH, GEN), f"tokens shape {toks.shape}")
    check(((toks >= 0) & (toks < cfg.vocab)).all(), "token id out of range")
    for r in range(2):
        log(f"  row {r}: {toks[r, :12].tolist()}")
    pf = kernel_counts(res.executables["prefill"])
    dc = kernel_counts(res.executables["decode"])
    log(f"tpu_custom_call kernels: prefill {dict(pf)} | decode {dict(dc)}")
    check(pf["quant_matmul"] > 0 and pf["prefill_attention"] > 0,
          "compiled prefill lacks the matmul or prefill-attention kernel")
    check(dc["quant_matmul"] > 0 and dc["decode_attention"] > 0,
          "compiled decode lacks the matmul or decode-attention kernel")

    # (d) continuous batching through the slot scheduler
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab, size=L,
                                               dtype=np.int32), max_gen=g)
            for i, (L, g) in enumerate(SCHED_REQS)]
    t0 = time.time()
    done = {c.rid: c for c in eng.generate(reqs, max_slots=SCHED_SLOTS)}
    log(f"scheduler: {len(done)} requests over {SCHED_SLOTS} slots in "
        f"{time.time() - t0:.1f} s wall incl. compile (informational)")
    check(sorted(done) == list(range(len(reqs))), "requests went missing")
    for rid, c in sorted(done.items()):
        check(c.status == "ok", f"request {rid} ended {c.status}: "
                                f"{c.reason}")
        check(len(c.tokens) == SCHED_REQS[rid][1],
              f"request {rid}: {len(c.tokens)} tokens, budget "
              f"{SCHED_REQS[rid][1]}")
    log(f"  statuses: {Counter(c.status for c in done.values())}; "
        f"rid 1 -> {done[1].tokens[:8]}")

    # (e) agreement with the XLA int8 path on the same params
    ref = Engine(eng.model, cfg,
                 dataclasses.replace(eng.policy, use_pallas=False),
                 eng.serve_params, eng.qparams, mode=eng.mode,
                 cache_layout=eng.cache_layout)
    got = forced_logits(eng, batch)[:, 0]
    want = forced_logits(ref, batch)[:, 0]
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite prefill logits")
    rel, rank = agreement(got, want)
    log(f"prefill logits vs XLA int8: relative L2 per row {rel.tolist()} "
        f"(tolerance {LOGIT_TOL}); max|diff| {np.abs(got - want).max()}")
    check(bool((rel <= LOGIT_TOL).all()), "prefill logits disagree")
    pick = got.argmax(-1)
    log(f"first greedy token: kernels {pick.tolist()} | XLA "
        f"{want.argmax(-1).tolist()} | rank in XLA logits {rank.tolist()}")
    check(bool((rank < TOP_K).all()),
          f"a first greedy token is outside the XLA path's top {TOP_K}")
    check(bool((toks[:, 0] == pick).all()),
          "generate_batch's first token is not the prefill argmax")
    for name, err in kernel_parity(cfg, seed).items():
        log(f"kernel {name} vs oracle: max|diff|/max|ref| {err}")
        check(err <= KERNEL_TOL, f"{name} disagrees with its oracle")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": info}), flush=True)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.dist import constraints
    from repro.launch.engine import Engine
    from repro.shard.engine import ShardedEngine

    info = device_info(jax, SP)
    devs = jax.devices()[:SP]

    t0 = time.time()
    sharded = ShardedEngine.from_checkpoint(ARCH, smoke=False, sp=SP,
                                            cache_layout="dense",
                                            init_seed=seed)
    single = Engine.from_checkpoint(ARCH, smoke=False, cache_layout="dense",
                                    init_seed=seed)
    log(f"engines built in {time.time() - t0:.1f} s (set-up)")
    batch = prompts(single.cfg, seed)

    t0 = time.time()
    res = sharded.generate_batch(batch, GEN)
    log(f"sp=4 generate_batch wall {time.time() - t0:.1f} s incl. compile "
        f"(informational); decode kernels "
        f"{dict(kernel_counts(res.executables['decode']))}")
    check(np.asarray(res.tokens).shape == (BATCH, GEN),
          f"tokens shape {np.asarray(res.tokens).shape}")
    problems = compare_sp(sharded, single, batch, np.asarray(res.tokens))

    report = sharded.dry_run_report(batch=BATCH, prompt_len=PROMPT)
    kinds = {k: v["collective_by_kind"]
             for k, v in report["executables"].items()}
    log(f"int8_all_reduces_ok {report['int8_all_reduces_ok']}; "
        f"collectives {kinds}")
    if not report["int8_all_reduces_ok"]:
        problems.append("a float all-reduce on the wire")
    # serving installs no activation-sharding rules (only the dry-run
    # planner does), so no constraint is requested here, let alone dropped
    if constraints.installed() is not None:
        problems.append("activation sharding rules are installed on the "
                        "serving path")

    params = sum(x.nbytes for x in jax.tree.leaves(sharded.serve_params))
    peaks = []
    for d in devs:
        s = d.memory_stats() or {}
        peaks.append(s.get("peak_bytes_in_use", 0))
        log(f"  {d}: bytes_in_use {s.get('bytes_in_use')} peak "
            f"{s.get('peak_bytes_in_use')}")
    if not all(p >= params // 2 for p in peaks):
        problems.append(f"params ({params} bytes) did not reach every "
                        f"device: {peaks}")
    check(not problems, "; ".join(problems))
    print(json.dumps({"ok": True, "device": info}), flush=True)


def split_merge_decode(q, view, k_scale, v_scale, cur_pos):
    """One-chip decode attention computed the way sp=4 computes it: the
    partials kernel over each quarter of the cache, then the same merge
    (``merge_partials``) — the sharded arithmetic without the mesh.
    Stands in for ``ops.decode_attention_view`` on a dense cache."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.shard.partial_softmax import merge_partials

    b, s = view.k.shape[:2]
    n = s // SP
    valid = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32).reshape(-1),
                             (b,))
    parts = [ops.decode_attention_partials(
        q, view.k[:, i * n:(i + 1) * n], view.v[:, i * n:(i + 1) * n],
        k_scale, v_scale, jnp.clip(valid - i * n, 0, n), kv_bits=view.bits)
        for i in range(SP)]
    acc, m, l = (jnp.stack(p) for p in zip(*parts))
    return merge_partials(m[..., None], l[..., None],
                          acc[..., None, :])[:, 0]


def frontier_slot(partials_view):
    """Planted fault: the shard that holds the newest token also attends
    the next, unwritten slot — its local valid count one too high."""
    import jax.numpy as jnp

    def faulty(q, view, k_scale, v_scale, cur_pos):
        s_local = view.k.shape[1]
        cur_pos = jnp.where((cur_pos > 0) & (cur_pos < s_local),
                            cur_pos + 1, cur_pos)
        return partials_view(q, view, k_scale, v_scale, cur_pos)
    return faulty


def dropped_shard(combine):
    """Planted fault: shard 1's partials never reach the merge."""
    import jax
    import jax.numpy as jnp

    from repro.models.attention import NEG_INF

    def faulty(m, l, acc, axis_name):
        keep = jax.lax.axis_index(axis_name) != 1
        return combine(jnp.where(keep, m, NEG_INF), l * keep, acc * keep,
                       axis_name)
    return faulty


def compare_sp(sharded, single, batch, sp_tokens) -> list:
    """sp=4 against one chip on the same params and prompts.  Returns
    the checks that failed; prints every reading first."""
    from unittest import mock

    import numpy as np

    from repro.kernels import ops
    from repro.shard import partial_softmax

    want = np.asarray(single.generate_batch(batch, GEN).tokens)
    same = sp_tokens == want
    log(f"free-running greedy tokens equal: {int(same.sum())} of "
        f"{same.size}; first difference per row "
        f"{[int(np.argmin(np.append(r, False))) for r in same]} "
        f"(informational: {GEN} means none)")
    # A near-tie flipped once changes every later input of a free-running
    # decode.  Teacher forcing feeds every run the one-chip tokens, so
    # each step compares the same inputs.
    force = want[:, :-1]
    lg_one = forced_logits(single, batch, force)
    with mock.patch.object(ops, "decode_attention_view", split_merge_decode):
        lg_merge = forced_logits(single, batch, force)
    runs = {"sound": forced_logits(sharded, batch, force)}
    with mock.patch.object(ops, "decode_attention_partials_view",
                           frontier_slot(ops.decode_attention_partials_view)):
        runs["frontier_slot"] = forced_logits(sharded, batch, force)
    with mock.patch.object(partial_softmax, "sp_partial_combine",
                           dropped_shard(partial_softmax.sp_partial_combine)):
        runs["dropped_shard"] = forced_logits(sharded, batch, force)

    def per_step(rel):
        return [float(x) for x in rel.max(0)]

    rel, rank = agreement(lg_merge, lg_one)
    log(f"one chip, four-way merge vs one-chip kernel: relative L2 max "
        f"{rel.max()} median {float(np.median(rel))}, worst rank "
        f"{int(rank.max())}; per step {per_step(rel)}")
    problems = []
    for name, lg in runs.items():
        r_merge = agreement(lg, lg_merge)[0]
        r_one, rank = agreement(lg, lg_one)
        log(f"sp=4 {name}: vs four-way merge max {r_merge.max()} "
            f"(tolerance {MERGE_TOL}); vs one-chip kernel max "
            f"{r_one.max()} median {float(np.median(r_one))} (tolerance "
            f"{SP_TOL}), greedy picks equal {int((rank == 0).sum())} of "
            f"{rank.size}, worst rank {int(rank.max())}; prefill step "
            f"max|diff| {np.abs(lg[:, 0] - lg_one[:, 0]).max()}")
        log(f"  per step vs merge {per_step(r_merge)}")
        log(f"  per step vs one chip {per_step(r_one)}")
        passes = (np.isfinite(lg).all() and r_merge.max() <= MERGE_TOL
                  and r_one.max() <= SP_TOL and rank.max() < TOP_K)
        if name == "sound" and not passes:
            problems.append("sp=4 logits disagree with one chip")
        elif name != "sound" and passes:
            problems.append(f"planted fault {name} passed the checks")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the one-chip serving phases; 4: only the "
                         "sequence-parallel phase against one chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    (one_chip if args.chips == 1 else four_chips)(args.seed)


if __name__ == "__main__":
    main()
