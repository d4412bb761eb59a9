"""Serving-path benchmark: prefill / decode timing across the int8 grid.

Measures steady-state (post-compile) wall time for the serving
configurations of the two-kernel engine:

  * weights: bf16 vs int8
  * KV cache: bf16 vs int8
  * prefill: chunked-jnp flash vs fused Pallas flash-prefill
    (quantize-once int8 attention) vs chunked ragged pipeline
  * decode driver: per-token Python loop vs single lax.scan
  * ragged traffic: mixed-length requests streaming through the
    slot-based continuous-batching scheduler (tokens/s under streaming
    admission — the multi-user serving number)
  * paged prefix reuse: repeated prompts through the PAGED cache layout —
    admissions ride the prefix store's shared pages instead of running
    prefill (the ``paged_prefix_reuse`` entry records hits and skipped
    prefill calls; CI requires it)
  * speculative decoding: repetitive prompts through the prompt-lookup
    ``SpeculativeStrategy`` vs greedy (the ``speculative_decode`` entry
    records draft acceptance rate, tok/s vs greedy, and the
    tokens-match-greedy bit; CI requires it well-formed)
  * degraded traffic: mixed-priority staggered arrivals under a
    deterministic fault plan (launch/faults.py) — one injected fault per
    class, a bounded admission queue, deadlines, and preemption; the
    ``degraded_traffic`` entry records goodput, the per-status census,
    deadline hit rate, and re-admit overhead (CI requires it)
  * crash recovery: a journaled run killed at a decode-block boundary by
    a simulated crash, then replayed via ``SlotScheduler.recover`` on a
    fresh scheduler — the ``crash_recovery`` entry records the cold
    recovery wall time, replayed tokens, and the bit-parity check
    against an uninterrupted run (CI requires it)

Each grid point is one ``Engine`` (launch/engine.py) — the same assembly
the serving CLI runs, so the bench measures the served configuration,
not a reimplementation of it.  The fine-grained pieces (loop-vs-scan,
fused-vs-jnp prefill) are timed on the engine's own step functions.

and writes ``BENCH_serve.json`` so the perf trajectory is tracked across
PRs.  The headline numbers are prefill ms / tokens-per-s per config plus
decode ms/token; the scan/loop ratio is the dispatch-overhead win, the
int8/bf16 and fused/jnp ratios are the bandwidth win (visible on real
HBM-bound hardware; on this CPU container the Pallas numbers run the
interpret lowering, so they track correctness and grid overhead, not the
2x byte reduction).

Run: PYTHONPATH=src python -m benchmarks.serve_bench [--quick] [--gen 32]
     [--prompt-len 512] [--prefill-chunk 128] [--max-slots 4]
     [--page-size 16]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.data import pipeline as DP
from repro.launch import steps as ST
from repro.launch.engine import Engine
from repro.launch.serve import ragged_requests


def _bench(fn, *args, iters=2):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _engine(memo, arch, *, int8_weights, kv_int8, calib_batches,
            **engine_kw):
    """One Engine per (weights, kv) config, memoized so the ragged and
    paged scenarios reuse the grid's calibration + conversion instead of
    paying a second end-to-end prepare pass."""
    key = (bool(int8_weights), bool(kv_int8))
    if key not in memo:
        memo[key] = Engine.from_checkpoint(
            arch, smoke=True, fp=not int8_weights, kv_int8=kv_int8,
            use_pallas=False, calib_batches=calib_batches, **engine_kw)
    return memo[key]


def bench_config(engine: Engine, batch, *, requests, prompt_len, gen,
                 prefill_chunk=None):
    model, cfg, policy = engine.model, engine.cfg, engine.policy
    mode = engine.mode
    serve_params, qparams = engine.serve_params, engine.qparams

    prefill = jax.jit(ST.make_prefill_step(model, cfg, policy, mode=mode))
    step = jax.jit(ST.make_serve_step(model, cfg, policy, mode=mode))
    loop = jax.jit(ST.make_decode_loop(model, cfg, policy, mode=mode,
                                       n_steps=gen))
    max_len = prompt_len + gen
    kv_int8 = bool(policy.kv_int8)
    cache0 = model.init_cache(requests, max_len, cfg.dtype, kv_int8=kv_int8)

    prefill_s = _bench(prefill, serve_params, qparams, batch, cache0)
    n_prompt = requests * prompt_len
    extra = {}
    if mode == "int8" or kv_int8:
        # fused flash-prefill: quantize-once attention over the int8 (or
        # unit-scale bf16) KV tiles via the Pallas kernel
        pol_f = dataclasses.replace(policy, use_pallas=True)
        prefill_f = jax.jit(ST.make_prefill_step(model, cfg, pol_f,
                                                 mode=mode))
        fused_s = _bench(prefill_f, serve_params, qparams, batch, cache0)
        extra["prefill_fused_ms"] = fused_s * 1e3
        extra["prefill_fused_tokens_per_s"] = n_prompt / fused_s
    if prefill_chunk:
        ctoks, lengths = ST.pad_for_chunked_prefill(batch["tokens"],
                                                    prefill_chunk)
        cbatch = {"tokens": ctoks}
        # cache sized for the PADDED prompt (whole chunks are written)
        cache_c = model.init_cache(requests, ctoks.shape[1] + gen, cfg.dtype,
                                   kv_int8=kv_int8)
        prefill_c = jax.jit(ST.make_prefill_step(
            model, cfg, policy, mode=mode, prefill_chunk=prefill_chunk))
        chunked_s = _bench(prefill_c, serve_params, qparams, cbatch, cache_c,
                           lengths)
        extra["prefill_chunked_ms"] = chunked_s * 1e3
        extra["prefill_chunked_tokens_per_s"] = n_prompt / chunked_s

    logits, cache = prefill(serve_params, qparams, batch, cache0)
    tok0 = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    def python_loop(tok0, cache):
        tok = tok0
        for i in range(gen - 1):
            tok, _, cache = step(serve_params, qparams, tok[:, None], cache,
                                 prompt_len + i)
        return tok

    def scan_loop(tok0, cache):
        toks, _ = loop(serve_params, qparams, tok0, cache, prompt_len)
        return toks

    loop_s = _bench(python_loop, tok0, cache)
    scan_s = _bench(scan_loop, tok0, cache)
    n_tok = max(gen - 1, 1)
    return {
        "prefill_ms": prefill_s * 1e3,
        "prefill_tokens_per_s": n_prompt / prefill_s,
        **extra,
        "decode_loop_ms_per_tok": loop_s / n_tok * 1e3,
        "decode_scan_ms_per_tok": scan_s / n_tok * 1e3,
        "decode_scan_tokens_per_s": requests * n_tok / scan_s,
        "scan_speedup_vs_loop": loop_s / scan_s,
    }


def _run_sched(engine, reqs, *, max_slots, prompt_len, gen, block_steps):
    """Warm once, then time a steady-state scheduler run."""
    engine.generate(list(reqs), max_slots=max_slots, prompt_cap=prompt_len,
                    gen_cap=gen, block_steps=block_steps)
    t0 = time.perf_counter()
    completions = engine.generate(list(reqs), max_slots=max_slots,
                                  prompt_cap=prompt_len, gen_cap=gen,
                                  block_steps=block_steps)
    wall = time.perf_counter() - t0
    sched = engine.make_scheduler(max_slots=max_slots,
                                  prompt_cap=prompt_len, gen_cap=gen,
                                  block_steps=block_steps)
    return completions, wall, sched


def bench_ragged_traffic(engine: Engine, *, requests, max_slots, prompt_len,
                         gen, block_steps=8):
    """Continuous-batching throughput: ``requests`` mixed-length requests
    stream through ``max_slots`` slots (launch/scheduler.py).  The first
    run compiles the three scheduler executables; the timed run is
    steady-state.  Records generated tokens/s — the multi-user serving
    headline — plus the executable counts (must be 1 each: raggedness is
    data, not shape)."""
    shape = ShapeSpec("bench", "train", prompt_len, requests)
    spec = DP.spec_for(engine.cfg, shape)
    reqs = ragged_requests(spec, requests, prompt_len, gen)
    completions, wall, sched = _run_sched(
        engine, reqs, max_slots=max_slots, prompt_len=prompt_len, gen=gen,
        block_steps=block_steps)
    n_new = sum(len(c.tokens) for c in completions)
    n_prompt = sum(c.prompt_len for c in completions)
    return {
        "requests": requests,
        "max_slots": max_slots,
        "block_steps": block_steps,
        "prompt_lens": sorted({c.prompt_len for c in completions}),
        "generated_tokens": n_new,
        "wall_ms": wall * 1e3,
        "gen_tokens_per_s": n_new / wall,
        "total_tokens_per_s": (n_new + n_prompt) / wall,
        "executables": sched.executable_counts(),
    }


def bench_speculative_decode(greedy_eng: Engine, spec_eng: Engine, *,
                             requests, max_slots, prompt_len, gen,
                             block_steps=8):
    """Prompt-lookup speculative decoding vs greedy through the slot
    scheduler, on REPETITIVE prompts (a short token pattern tiled to the
    prompt length — the lookup needs recurring n-grams to draft from).
    Records the draft acceptance rate, tok/s for both strategies, and
    ``tokens_match`` — speculative output must be BIT-IDENTICAL to
    greedy under the deterministic accept rule, so the bench doubles as
    an end-to-end correctness check.  On this CPU container the verify
    window runs emulated, so treat the speedup as a dispatch-count
    proxy, not an HBM-bandwidth number (that is what the int8 cache
    halves on real hardware)."""
    shape = ShapeSpec("bench", "train", prompt_len, requests)
    spec = DP.spec_for(greedy_eng.cfg, shape)
    reqs = []
    for r in ragged_requests(spec, requests, prompt_len, gen):
        t = np.asarray(r.tokens)
        pat = t[:min(8, len(t))]
        tiled = np.tile(pat, -(-len(t) // len(pat)))[:len(t)]
        reqs.append(dataclasses.replace(r, tokens=tiled.astype(np.int32)))

    g_done, g_wall, _ = _run_sched(
        greedy_eng, reqs, max_slots=max_slots, prompt_len=prompt_len,
        gen=gen, block_steps=block_steps)
    s_done, s_wall, sched = _run_sched(
        spec_eng, reqs, max_slots=max_slots, prompt_len=prompt_len,
        gen=gen, block_steps=block_steps)
    g_tokens = {c.rid: c.tokens for c in g_done}
    s_tokens = {c.rid: c.tokens for c in s_done}
    n_new = sum(len(t) for t in s_tokens.values())
    stats = sched.spec_stats()
    return {
        "requests": requests,
        "max_slots": max_slots,
        "spec_k": stats["draft_k"],
        "spec_ngram": spec_eng.spec_ngram,
        "generated_tokens": n_new,
        "wall_ms": s_wall * 1e3,
        "gen_tokens_per_s": n_new / s_wall,
        "greedy_gen_tokens_per_s":
            sum(len(t) for t in g_tokens.values()) / g_wall,
        "speedup_vs_greedy": g_wall / s_wall,
        "acceptance_rate": stats["acceptance_rate"],
        "tokens_per_window": stats["tokens_per_window"],
        "tokens_match": g_tokens == s_tokens,
        "executables": sched.executable_counts(),
    }


def bench_paged_prefix_reuse(engine: Engine, *, requests, max_slots,
                             prompt_len, gen, block_steps=8):
    """Prefix sharing under the paged layout: a queue where every request
    carries the SAME prompt.  After the first admission registers the
    prompt's pages, every later admission attaches them with zero prefill
    FLOPs — ``prefill_calls_saved`` counts the skipped executions and
    ``shared_tokens`` the prompt tokens served from shared pages."""
    shape = ShapeSpec("bench", "train", prompt_len, requests)
    spec = DP.spec_for(engine.cfg, shape)
    base = ragged_requests(spec, 1, prompt_len, gen)[0]
    reqs = [dataclasses.replace(base, rid=r) for r in range(requests)]
    completions, wall, sched = _run_sched(
        engine, reqs, max_slots=max_slots, prompt_len=prompt_len, gen=gen,
        block_steps=block_steps)
    n_new = sum(len(c.tokens) for c in completions)
    stats = sched.prefix_stats()
    calls = sched.call_counts()
    admissions = 2 * requests          # warm run + timed run
    return {
        "requests": requests,
        "max_slots": max_slots,
        "page_size": sched.page_size,
        "generated_tokens": n_new,
        "wall_ms": wall * 1e3,
        "gen_tokens_per_s": n_new / wall,
        "prefill_calls": calls["prefill"],
        "prefill_calls_saved": admissions - calls["prefill"],
        "prefix_hits": stats["hits"],
        "shared_tokens": stats["shared_tokens"],
        "executables": sched.executable_counts(),
    }


def bench_degraded_traffic(engine: Engine, *, prompt_len, gen,
                           block_steps=4):
    """Resilience scenario: fixed-seed staggered arrivals with mixed
    priorities and deadlines through a 2-slot scheduler under a
    deterministic :class:`repro.launch.faults.FaultPlan` — one injected
    fault per class (bad request, NaN logits mid-decode, forced prefix
    exhaustion, forced preemption) plus a bounded admission queue that
    sheds under overload and a virtual clock (``ms_per_block``) that
    makes the deadline/arrival interleaving bit-reproducible.

    Records GOODPUT (tokens from requests that finished ok, per second),
    the per-status census, the deadline hit rate, and the preemption
    re-admit overhead (``resume`` prefill calls) — the numbers that show
    faults stay contained to the requests that own them."""
    n = 9
    shape = ShapeSpec("bench", "train", prompt_len, n)
    spec = DP.spec_for(engine.cfg, shape)
    base = ragged_requests(spec, n, prompt_len, gen)
    half = max(1, prompt_len // 2)

    def mk(r, **kw):
        return dataclasses.replace(base[r], **kw)

    reqs = [
        # long-running low-priority resident: forced-preempted at block 1,
        # priority-preempted when rid4 arrives, re-admitted both times
        mk(0, tokens=np.asarray(base[0].tokens)[:half], max_gen=2 * gen,
           arrive_ms=0.0),
        # same shape but with a generous deadline it makes -> the "hit"
        mk(1, tokens=np.asarray(base[1].tokens)[:half], max_gen=2 * gen,
           deadline_ms=500.0, arrive_ms=0.0),
        # malformed arrival: empty prompt -> rejected at admission
        mk(2, tokens=np.zeros((0,), np.int32), arrive_ms=0.0),
        # NaN logits injected at its decode step 1 -> failed, isolated
        mk(3, arrive_ms=0.0),
        # priority arrival mid-run -> preempts the lowest-priority slot
        mk(4, priority=1, arrive_ms=15.0),
        # tight deadline spent waiting in the queue -> timeout
        mk(5, deadline_ms=15.0, arrive_ms=0.0),
        mk(6, arrive_ms=5.0),
        mk(7, arrive_ms=5.0),
        # ninth arrival against queue_cap=5 -> shed
        mk(8, arrive_ms=5.0),
    ]

    sched = engine.make_scheduler(max_slots=2, prompt_cap=prompt_len,
                                  gen_cap=2 * gen, block_steps=block_steps)
    # warm run compiles the executables; the fault plan is a pure function
    # of (rid, block, step) so both runs see identical injections
    engine.generate(list(reqs), max_slots=2, prompt_cap=prompt_len,
                    gen_cap=2 * gen, block_steps=block_steps)
    h0, c0 = dict(sched.health_stats()), dict(sched.call_counts())
    t0 = time.perf_counter()
    completions = engine.generate(list(reqs), max_slots=2,
                                  prompt_cap=prompt_len, gen_cap=2 * gen,
                                  block_steps=block_steps)
    wall = time.perf_counter() - t0
    h1, c1 = sched.health_stats(), sched.call_counts()
    dh = {k: h1[k] - h0.get(k, 0) for k in h1}

    statuses: dict = {}
    for c in completions:
        statuses[c.status] = statuses.get(c.status, 0) + 1
    ok_tokens = sum(len(c.tokens) for c in completions if c.status == "ok")
    with_deadline = [c for c in completions
                     if next(r for r in reqs if r.rid == c.rid).deadline_ms
                     is not None]
    hits = sum(1 for c in with_deadline if c.status == "ok")
    return {
        "requests": n,
        "max_slots": 2,
        "block_steps": block_steps,
        "queue_cap": engine.queue_cap,
        "shed_policy": engine.shed_policy,
        "fault_plan": engine.fault_plan.describe(),
        "statuses": statuses,
        "generated_ok_tokens": ok_tokens,
        "wall_ms": wall * 1e3,
        "goodput_tokens_per_s": ok_tokens / wall,
        "deadline_hit_rate": hits / max(len(with_deadline), 1),
        "preemptions": dh.get("preemptions", 0),
        "readmits": dh.get("readmits", 0),
        "resume_prefill_calls": c1.get("resume", 0) - c0.get("resume", 0),
        "deadline_misses": dh.get("deadline_misses", 0),
        "prefix_exhausted": dh.get("prefix_exhausted", 0),
        "executables": sched.executable_counts(),
    }


def bench_crash_recovery(engine: Engine, *, prompt_len, gen,
                         block_steps=4, crash_block=1):
    """Durability scenario: the same mixed-length queue is served three
    times — an uninterrupted reference run, a journaled run killed by a
    :class:`SimulatedCrash` at ``crash_block``, and a journal-replay
    recovery (``SlotScheduler.recover``) on a FRESH scheduler.  Recovery
    is COLD on purpose: a real restarted process pays executable
    compilation + journal replay + resume prefills + the remaining
    decode, and ``recovery_ms`` measures exactly that (the reference run
    is equally cold, so ``clean_wall_ms`` is the like-for-like number).
    ``tokens_match`` asserts the acceptance property end to end: the
    recovered completions are bit-identical to the uninterrupted run,
    statuses included.  ``crash_block`` defaults to the first boundary —
    every admitted resident is still mid-generation there (any
    ``gen > block_steps + 1``), so the replay always exercises the
    re-admission path."""
    import tempfile

    from repro.launch.faults import FaultPlan, SimulatedCrash
    from repro.launch.scheduler import SlotScheduler

    n = 6
    shape = ShapeSpec("bench", "train", prompt_len, n)
    spec = DP.spec_for(engine.cfg, shape)
    reqs = ragged_requests(spec, n, prompt_len, gen)

    def sched(**extra):
        return SlotScheduler(
            engine.model, engine.cfg, engine.policy, engine.serve_params,
            engine.qparams, mode=engine.mode, max_slots=2,
            prompt_cap=prompt_len, gen_cap=gen, block_steps=block_steps,
            prefill_chunk=engine.prefill_chunk, **extra)

    with tempfile.TemporaryDirectory() as tmp:
        journal = f"{tmp}/requests.jsonl"
        t0 = time.perf_counter()
        ref = sched().run(list(reqs))
        clean_wall = time.perf_counter() - t0

        crashed = sched(journal=journal,
                        fault_plan=FaultPlan(crash=(crash_block,)))
        try:
            crashed.run(list(reqs))
            raise RuntimeError("fault plan failed to crash the run")
        except SimulatedCrash:
            pass

        rec = sched(journal=journal)
        t0 = time.perf_counter()
        done = rec.recover()
        recovery_wall = time.perf_counter() - t0

    def by_rid(cs):
        return {c.rid: (tuple(c.tokens), c.status, c.finished_by)
                for c in cs}

    health, calls = rec.health_stats(), rec.call_counts()
    return {
        "requests": n,
        "max_slots": 2,
        "block_steps": block_steps,
        "crash_block": crash_block,
        "generated_tokens": sum(len(c.tokens) for c in done),
        "clean_wall_ms": clean_wall * 1e3,
        "recovery_ms": recovery_wall * 1e3,
        "recoveries": health["recoveries"],
        "replayed_tokens": health["replayed_tokens"],
        "resume_prefill_calls": calls.get("resume", 0),
        "tokens_match": by_rid(done) == by_rid(ref),
        "executables": rec.executable_counts(),
    }


def bench_sharded_decode(arch, *, requests, prompt_len, gen):
    """Sharded serving scenario: tp=2 vs tp=1 on a host-local mesh.

    Both engines are built from the SAME seed at a shard-divisible head
    grid (the smoke preset's 3 heads can't split), so ``tokens_match``
    is the tensor-parallel exactness claim — int32 row epilogues make
    tp=2 bit-identical, not merely close.  The timing columns are
    dispatch proxies (on CPU the mesh is emulated host-local devices;
    real interconnect wins need hardware), and the byte columns come
    from the compiled HLO via ``dry_run_report``: total collective
    bytes per executable plus the integer-all-reduce verdict CI gates
    on.  Returns None below 2 devices (the bench-smoke lane sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    if jax.device_count() < 2:
        return None
    from repro.shard.engine import ShardedEngine

    cfg = get_config(arch, smoke=True)
    cfg = cfg.replace(n_heads=4, n_kv_heads=2, head_dim=cfg.head_dim)
    kw = dict(cfg=cfg, smoke=True, cache_layout="dense", use_pallas=False)
    base = Engine.from_checkpoint(arch, **kw)
    tp2 = ShardedEngine.from_checkpoint(arch, tp=2, **kw)

    shape = ShapeSpec("bench", "train", prompt_len, requests)
    spec = DP.spec_for(cfg, shape)
    batch = DP.make_batch(spec, 12345)
    batch.pop("labels", None)

    def run(eng):
        eng.generate_batch(batch, gen, prompt_len=prompt_len)  # compile
        return eng.generate_batch(batch, gen, prompt_len=prompt_len)

    r1, r2 = run(base), run(tp2)
    hlo = tp2.dry_run_report(batch=requests, prompt_len=prompt_len)
    ex = hlo["executables"]
    return {
        "requests": requests,
        "prompt_len": prompt_len,
        "gen": gen,
        "tp": 2,
        "tokens_match": bool(np.array_equal(np.asarray(r1.tokens),
                                            np.asarray(r2.tokens))),
        "prefill_ms_tp1": r1.prefill_s * 1e3,
        "prefill_ms_tp2": r2.prefill_s * 1e3,
        "decode_ms_per_tok_tp1": r1.decode_s / max(gen - 1, 1) * 1e3,
        "decode_ms_per_tok_tp2": r2.decode_s / max(gen - 1, 1) * 1e3,
        "prefill_collective_bytes": ex["prefill"]["collective_bytes"],
        "decode_collective_bytes": ex["decode"]["collective_bytes"],
        "decode_all_reduce_bytes": float(sum(
            b for _, b in ex["decode"]["all_reduce_payloads"])),
        "int8_all_reduces_ok": bool(hlo["int8_all_reduces_ok"]),
    }


def bench_int4_kv(eng8: Engine, *, requests, prompt_len, gen):
    """int4 packed KV cache vs int8: exact byte halving of the quantized
    KV payload, plus serving throughput of the packed lane.

    Both engines share the memoized int8 preparation — the §2 max-abs
    thresholds are bit-width independent (T = max|x|; only the derived
    scale T/levels changes), so swapping ``kv_bits`` on the policy is
    the whole reconfiguration.  The kernels fold the nibble unpack into
    their dequant epilogue, so the dispatch count per generated token is
    identical to int8 — tokens/s is the honest throughput proxy while
    the cache byte counts carry the capacity story (2x more resident
    sequence per HBM byte)."""
    eng4 = Engine(eng8.model, eng8.cfg,
                  dataclasses.replace(eng8.policy, kv_bits=4),
                  eng8.serve_params, eng8.qparams, mode=eng8.mode,
                  cache_layout="dense")
    eng8d = Engine(eng8.model, eng8.cfg, eng8.policy, eng8.serve_params,
                   eng8.qparams, mode=eng8.mode, cache_layout="dense")

    def kv_cache_bytes(eng):
        # shape-only: the quantized KV payload is exactly the int8 leaves
        # (packed nibbles ride int8 storage at kv_bits=4)
        max_len = eng._cache_len(prompt_len, gen)
        abstract = jax.eval_shape(lambda: eng.init_cache(requests, max_len))
        return int(sum(l.size for l in jax.tree.leaves(abstract)
                       if l.dtype == jnp.int8))

    b8, b4 = kv_cache_bytes(eng8d), kv_cache_bytes(eng4)
    shape = ShapeSpec("bench_i4", "train", prompt_len, requests)
    spec = DP.spec_for(eng8.cfg, shape)
    batch = DP.make_batch(spec, 777)
    batch.pop("labels", None)
    r8 = eng8d.generate_batch(batch, gen, prompt_len=prompt_len)
    r4 = eng4.generate_batch(batch, gen, prompt_len=prompt_len)
    return {
        "requests": requests,
        "prompt_len": prompt_len,
        "gen": gen,
        "cache_bytes_int8": b8,
        "cache_bytes_int4": b4,
        "cache_bytes_ratio": b8 / b4,
        "prefill_ms_int4": r4.prefill_s * 1e3,
        "gen_tokens_per_s_int8": r8.gen_tokens / max(r8.decode_s, 1e-9),
        "gen_tokens_per_s_int4": r4.gen_tokens / max(r4.decode_s, 1e-9),
    }


def main():
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    help="architecture preset to benchmark")
    ap.add_argument("--requests", type=int, default=4,
                    help="synthetic requests per scenario")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="tokens per synthetic prompt")
    ap.add_argument("--gen", type=int, default=32,
                    help="tokens to generate per request")
    ap.add_argument("--quick", action="store_true",
                    help="only the production config (int8 w + int8 kv)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="also time the chunked ragged prefill pipeline")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="slots for the ragged-traffic scenario "
                         "(default: requests // 2, min 2)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="page size for the paged prefix-reuse scenario")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft-window length for the speculative scenario")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup n-gram for the speculative scenario")
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="path for the benchmark JSON report")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    shape = ShapeSpec("cli", "train", args.prompt_len, args.requests)
    spec = DP.spec_for(cfg, shape)
    calib_batches = DP.calibration_batches(spec, 2)
    for b in calib_batches:
        b.pop("labels", None)
    batch = DP.make_batch(spec, 12345)
    batch.pop("labels", None)

    grid = [("int8_w_int8_kv", True, True)]
    if not args.quick:
        grid += [
            ("bf16_w_bf16_kv", False, False),
            ("bf16_w_int8_kv", False, True),
            ("int8_w_bf16_kv", True, False),
        ]

    report = {
        "arch": args.arch,
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "prefill_chunk": args.prefill_chunk,
        "backend": jax.default_backend(),
        "configs": {},
    }
    memo = {}   # (int8_weights, kv_int8) -> Engine
    for name, int8_w, kv8 in grid:
        eng = _engine(memo, args.arch, int8_weights=int8_w, kv_int8=kv8,
                      calib_batches=calib_batches)
        r = bench_config(
            eng, batch, requests=args.requests, prompt_len=args.prompt_len,
            gen=args.gen, prefill_chunk=args.prefill_chunk)
        report["configs"][name] = r
        fused = (f" | fused {r['prefill_fused_ms']:.1f} ms"
                 if "prefill_fused_ms" in r else "")
        chunked = (f" | chunked {r['prefill_chunked_ms']:.1f} ms"
                   if "prefill_chunked_ms" in r else "")
        print(f"{name}: prefill {r['prefill_ms']:.1f} ms "
              f"({r['prefill_tokens_per_s']:.0f} tok/s){fused}{chunked} | "
              f"loop {r['decode_loop_ms_per_tok']:.2f} ms/tok | "
              f"scan {r['decode_scan_ms_per_tok']:.2f} ms/tok "
              f"({r['scan_speedup_vs_loop']:.2f}x, "
              f"{r['decode_scan_tokens_per_s']:.0f} tok/s)")

    cfgs = report["configs"]
    ref = cfgs.get("bf16_w_bf16_kv")
    # bf16_w_int8_kv isolates the fused int8 ATTENTION (the int8-weight
    # configs also swap every matmul kernel under use_pallas, which on the
    # CPU interpret lowering is a separate, unrelated cost)
    fus = cfgs.get("bf16_w_int8_kv") or next(
        (c for n, c in cfgs.items()
         if "int8_kv" in n and "prefill_fused_ms" in c), None)
    if ref and fus and "prefill_fused_ms" in fus:
        ratio = ref["prefill_ms"] / fus["prefill_fused_ms"]
        report["fused_int8_prefill_speedup_vs_bf16_jnp"] = ratio
        print(f"fused int8 prefill vs bf16 jnp prefill: {ratio:.2f}x")

    # continuous batching: stream 2x the slot count of mixed-length
    # requests through the scheduler (the multi-user serving scenario)
    slots = args.max_slots or max(2, args.requests // 2)
    n_reqs = max(args.requests, 2 * slots)
    block = min(8, max(2, args.gen // 2))
    eng = _engine(memo, args.arch, int8_weights=True, kv_int8=True,
                  calib_batches=calib_batches)
    eng.prefill_chunk = args.prefill_chunk
    rt = bench_ragged_traffic(
        eng, requests=n_reqs, max_slots=slots, prompt_len=args.prompt_len,
        gen=args.gen, block_steps=block)
    report["ragged_traffic"] = rt
    print(f"ragged traffic: {rt['requests']} reqs / {rt['max_slots']} slots "
          f"| lens {rt['prompt_lens']} | {rt['generated_tokens']} tokens in "
          f"{rt['wall_ms']:.1f} ms ({rt['gen_tokens_per_s']:.0f} gen tok/s) "
          f"| executables {rt['executables']}")

    # speculative decoding: repetitive prompts through the prompt-lookup
    # strategy vs greedy — a fresh engine (own scheduler) sharing the
    # memoized int8 preparation; tokens must match greedy bit-for-bit
    spec_eng = Engine(eng.model, eng.cfg, eng.policy, eng.serve_params,
                      eng.qparams, mode=eng.mode,
                      decode_strategy="speculative", spec_k=args.spec_k,
                      spec_ngram=args.spec_ngram,
                      prefill_chunk=args.prefill_chunk)
    sd = bench_speculative_decode(
        eng, spec_eng, requests=n_reqs, max_slots=slots,
        prompt_len=args.prompt_len, gen=args.gen, block_steps=block)
    report["speculative_decode"] = sd
    print(f"speculative decode: {sd['requests']} reqs / {sd['max_slots']} "
          f"slots | k={sd['spec_k']} ngram={sd['spec_ngram']} | acceptance "
          f"{sd['acceptance_rate']:.2f} ({sd['tokens_per_window']:.2f} "
          f"tok/window) | {sd['gen_tokens_per_s']:.0f} vs greedy "
          f"{sd['greedy_gen_tokens_per_s']:.0f} gen tok/s "
          f"({sd['speedup_vs_greedy']:.2f}x) | tokens_match="
          f"{sd['tokens_match']} | executables {sd['executables']}")

    # paged prefix reuse: the SAME prompt repeated — a fresh paged engine
    # (own scheduler/prefix store) sharing the memoized int8 preparation
    paged = Engine(eng.model, eng.cfg, eng.policy, eng.serve_params,
                   eng.qparams, mode=eng.mode, cache_layout="paged",
                   page_size=args.page_size,
                   prefill_chunk=args.prefill_chunk)
    pr = bench_paged_prefix_reuse(
        paged, requests=n_reqs, max_slots=slots,
        prompt_len=args.prompt_len, gen=args.gen, block_steps=block)
    report["paged_prefix_reuse"] = pr
    print(f"paged prefix reuse: {pr['requests']} identical prompts / "
          f"{pr['max_slots']} slots | {pr['prefill_calls']} prefill calls "
          f"({pr['prefill_calls_saved']} saved, {pr['shared_tokens']} "
          f"tokens from shared pages) | {pr['gen_tokens_per_s']:.0f} gen "
          f"tok/s | executables {pr['executables']}")

    # degraded traffic: staggered mixed-priority arrivals under a
    # deterministic fault plan — a fresh paged engine with a bounded
    # admission queue (own scheduler) sharing the int8 preparation
    from repro.launch.faults import FaultPlan
    deg_eng = Engine(eng.model, eng.cfg, eng.policy, eng.serve_params,
                     eng.qparams, mode=eng.mode, cache_layout="paged",
                     page_size=args.page_size,
                     prefill_chunk=args.prefill_chunk,
                     queue_cap=5, shed_policy="shed",
                     fault_plan=FaultPlan(nan_decode=((3, 1),),
                                          preempt=((1, 0),),
                                          exhaust_prefix=True,
                                          ms_per_block=10.0))
    dg = bench_degraded_traffic(deg_eng, prompt_len=args.prompt_len,
                                gen=args.gen)
    report["degraded_traffic"] = dg
    print(f"degraded traffic: {dg['requests']} reqs / {dg['max_slots']} "
          f"slots under faults | statuses {dg['statuses']} | goodput "
          f"{dg['goodput_tokens_per_s']:.0f} tok/s | deadline hit rate "
          f"{dg['deadline_hit_rate']:.2f} | {dg['preemptions']} preemptions "
          f"/ {dg['readmits']} readmits ({dg['resume_prefill_calls']} "
          f"resume prefills) | executables {dg['executables']}")

    # crash recovery: a journaled run killed mid-flight, then replayed on
    # a fresh scheduler — cost of coming back plus the bit-parity check
    cr = bench_crash_recovery(eng, prompt_len=args.prompt_len, gen=args.gen)
    report["crash_recovery"] = cr
    print(f"crash recovery: {cr['requests']} reqs crashed at block "
          f"{cr['crash_block']} | replay {cr['replayed_tokens']} tokens "
          f"({cr['resume_prefill_calls']} resume prefills) | recovery "
          f"{cr['recovery_ms']:.1f} ms vs clean {cr['clean_wall_ms']:.1f} "
          f"ms | tokens_match={cr['tokens_match']} | executables "
          f"{cr['executables']}")

    # int4 packed KV: exact cache-byte halving vs int8 + packed-lane
    # throughput, sharing the memoized int8 preparation
    i4 = bench_int4_kv(eng, requests=args.requests,
                       prompt_len=args.prompt_len, gen=args.gen)
    report["int4_kv"] = i4
    print(f"int4 kv: cache {i4['cache_bytes_int4']} B vs int8 "
          f"{i4['cache_bytes_int8']} B ({i4['cache_bytes_ratio']:.1f}x) | "
          f"{i4['gen_tokens_per_s_int4']:.0f} vs int8 "
          f"{i4['gen_tokens_per_s_int8']:.0f} gen tok/s")

    # sharded decode: tp=2 vs tp=1 bit parity + the compiled-HLO
    # collective audit (needs >= 2 devices; the CI lane forces 4)
    sh = bench_sharded_decode(args.arch, requests=args.requests,
                              prompt_len=args.prompt_len, gen=args.gen)
    report["sharded_decode"] = sh
    if sh is None:
        print("sharded decode: skipped (single device — set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    else:
        print(f"sharded decode: tp={sh['tp']} | tokens_match="
              f"{sh['tokens_match']} | decode "
              f"{sh['decode_ms_per_tok_tp2']:.2f} vs tp1 "
              f"{sh['decode_ms_per_tok_tp1']:.2f} ms/tok | collectives "
              f"prefill {sh['prefill_collective_bytes']:.0f} B / decode "
              f"{sh['decode_collective_bytes']:.0f} B (all-reduce "
              f"{sh['decode_all_reduce_bytes']:.0f} B) | "
              f"int8_all_reduces_ok={sh['int8_all_reduces_ok']}")

    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
