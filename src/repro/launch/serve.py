"""Serving CLI: int8 FAT-quantized model, batched requests, one Engine.

All assembly (calibrate -> convert_to_int8 -> step functions -> cache
layout) lives in ``launch/engine.py::Engine``; this module only parses
flags, builds requests, runs the engine, and prints.  The whole resident
state is int8: weights (the paper's "ready to run on mobile phones"
artifact, here TPU-shaped) AND the KV cache (per-head static thresholds
from the same §2 calibration pass, frozen at finalize_calibration) — so
BOTH attention phases stream half the HBM bytes and nothing is computed
"on the fly".

The engine is two fused Pallas kernels over the same int8 KV cache
(``--pallas``): flash-prefill and flash-decode, both reading KV tiles
through the cache's kernel view — an identity block table for the
dense/ring layouts, the page table for ``--cache-layout paged``.  The
decode loop is a single compiled ``jax.lax.scan`` over the generation
(steps.make_decode_loop): N tokens cost one dispatch instead of N.
``--loop`` keeps the legacy per-token Python loop for comparison.

``--prefill-chunk N`` switches prefill to the chunked ragged pipeline:
one lax.scan over fixed-size prompt chunks plus a per-request length
vector, so a single compiled executable serves any prompt length up to
the pad (no per-shape retrace).  ``--temperature`` / ``--top-p`` turn on
sampled decoding (greedy by default).

``--max-slots N`` switches the whole run to CONTINUOUS BATCHING
(launch/scheduler.py): the batch becomes N slots over one fixed-shape
int8 cache, requests stream in from a queue with ragged prompt lengths,
each admission runs the chunked prefill into a free slot's cache region,
and decode blocks advance every live slot at its own position — one
compiled decode executable for every admission pattern.

``--cache-layout {dense,ring,paged}`` picks the KV-cache layout behind
the ``repro.cache.KVCache`` protocol: ``ring`` (default) gives sliding-
window layers a window-sized ring buffer and everything else a dense
cache; ``dense`` forces absolute slots everywhere; ``paged`` switches to
a page pool + per-slot block tables (``--page-size``), which with
``--max-slots`` turns on prompt prefix sharing — a repeated prompt
admits with zero prefill FLOPs through the scheduler's prefix store.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 4 --prompt-len 32 --gen 16
  Flags: --fp (bf16 weights baseline)  --no-kv-int8 (bf16 KV cache)
         --kv-bits {8,4} (int4 packed KV cache at 4)
         --finetune-thresholds N (train KV thresholds by distillation)
         --loop (per-token dispatch instead of the scanned loop)
         --pallas (fused kernels; defaults on for TPU backends)
         --prefill-chunk N (chunked ragged prefill)
         --temperature T --top-p P --seed S (sampled decoding)
         --strategy {greedy,sample,speculative} (decode strategy; the
             DecodeStrategy protocol in launch/strategies.py —
             speculative = prompt-lookup drafting + one batched verify
             pass per window, bit-identical tokens to greedy)
         --spec-k N --spec-ngram N (speculative draft window / lookup
             n-gram; static, so no retrace across draft contents)
         --max-slots N (continuous-batching scheduler)
         --block-steps N --eos-id T (scheduler decode-block / EOS knobs)
         --cache-layout {dense,ring,paged} --page-size N (KV layout)
         --deadline-ms MS (per-request completion deadline; missed
             requests retire with status 'timeout' at block boundaries)
         --queue-cap N --shed-policy {shed,block} (bounded admission
             queue + overload behavior — graceful degradation)
         --fault-plan SPEC (inline JSON or a path to a JSON file; a
             launch/faults.py FaultPlan injecting deterministic faults —
             admission failures, NaN logits, forced preemptions, forced
             prefix-pool exhaustion, simulated crashes, virtual clock)
         --journal PATH (write-ahead request journal; a crashed run
             restarts with --restore journal and completes bit-identical
             — docs/serving.md "Durability & recovery")
         --snapshot-every N --snapshot-dir DIR (full state snapshot
             through the checkpoint manager every N decode blocks)
         --restore {journal,snapshot} (recover a crashed run instead of
             serving fresh requests)
         --strict (exit non-zero when any request retires non-'ok' —
             lets chaos CI and scripts gate on degraded runs)
         --spans PATH (write the run's scheduler spans as JSON lines at
             the end — docs/serving.md "Spans")
         --ckpt-dir DIR (restore trained params instead of random init)
         --tp N / --sp N (sharded serving over N devices: tensor-
             parallel projections with int32 psum epilogues / sequence-
             parallel KV with exact partial-softmax merge — mutually
             exclusive, both token-identical to the unsharded engine;
             docs/serving.md "Sharded serving")
         --mesh {auto,dryrun} (auto = build the serving mesh and serve;
             dryrun = compile the sharded executables, print the HLO
             collective audit — every serving-path all-reduce must carry
             integer payload bytes — and exit, 1 if the audit fails)

Every request retires with a terminal ``Completion.status`` (ok |
rejected | timeout | preempted | shed | failed — docs/serving.md
"Failure semantics"); the run prints the scheduler's health report
(per-status counts, preemptions, re-admits, deadline misses, recovery
counters).  A simulated crash (fault plan ``crash``) exits with code 3
after its journal/snapshot state is durable; ``--strict`` failures exit
with code 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.shapes import ShapeSpec
from repro.data import pipeline as DP
# re-export: the deployment pipeline lives with the Engine now
from repro.launch.engine import Engine, prepare_int8  # noqa: F401


def ragged_requests(spec, n_requests, prompt_len, gen, *, seed=12345,
                    deadline_ms=None):
    """Build a ragged request queue from the data pipeline: request r's
    prompt keeps between half and all of ``prompt_len`` tokens (a
    deterministic mixed-length arrival pattern).  ``deadline_ms`` applies
    one completion deadline to every request (None = none)."""
    from repro.launch.scheduler import Request

    batch = DP.make_batch(
        dataclasses.replace(spec, global_batch=n_requests), seed)
    toks = jax.device_get(batch["tokens"])[:, :prompt_len]
    reqs = []
    for r in range(n_requests):
        frac = (r % 4) / 6.0               # lengths cycle 1, 5/6, 2/3, 1/2
        length = max(1, prompt_len - int(frac * prompt_len))
        reqs.append(Request(rid=r, tokens=toks[r, :length].astype("int32"),
                            max_gen=gen, deadline_ms=deadline_ms))
    return reqs


def run_continuous(args, engine: Engine):
    """--max-slots path: stream --requests ragged requests through the
    slot scheduler (or, with --restore, pick a crashed run back up from
    its journal/snapshot) and report aggregate throughput."""
    from repro.launch import telemetry
    from repro.launch.faults import SimulatedCrash

    sched_kw = dict(max_slots=args.max_slots, prompt_cap=args.prompt_len,
                    gen_cap=args.gen, block_steps=args.block_steps,
                    eos_id=args.eos_id)
    t0 = time.time()
    try:
        if args.restore == "journal":
            completions = engine.recover(**sched_kw)
        elif args.restore == "snapshot":
            completions = engine.resume(**sched_kw)
        else:
            spec = DP.spec_for(
                engine.cfg, ShapeSpec("cli", "train", args.prompt_len,
                                      args.requests))
            reqs = ragged_requests(spec, args.requests, args.prompt_len,
                                   args.gen, deadline_ms=args.deadline_ms)
            completions = engine.generate(reqs, **sched_kw)
    except SimulatedCrash as e:
        # the boundary's journal records / snapshot landed BEFORE the
        # crash fired — the run is recoverable by a fresh process
        print(f"[serve] {e}")
        print("[serve] state is durable — restart with --restore journal "
              "(+ --journal PATH) or --restore snapshot "
              "(+ --snapshot-dir DIR) to finish the run bit-identically")
        raise SystemExit(3)
    finally:
        if args.spans:
            n = telemetry.tracer().write_jsonl(args.spans)
            print(f"[serve] spans: {n} records -> {args.spans}")
    wall = time.time() - t0
    sched = engine.make_scheduler(**sched_kw)
    n_new = sum(len(c.tokens) for c in completions)
    n_prompt = sum(c.prompt_len for c in completions)
    print(f"[serve] continuous batching ({sched.cache_layout}): "
          f"{len(completions)} requests "
          f"through {args.max_slots} slots (block={args.block_steps}) | "
          f"prompt lens {sorted({c.prompt_len for c in completions})} | "
          f"{n_new} tokens in {wall*1e3:.1f} ms "
          f"({n_new/max(wall,1e-9):.0f} gen tok/s, "
          f"{(n_new+n_prompt)/max(wall,1e-9):.0f} total tok/s)")
    counts = sched.executable_counts()
    print(f"[serve] executables: " +
          " ".join(f"{k}={v}" for k, v in counts.items()) +
          " (1 each == no retrace across the whole ragged run)")
    by_status: dict = {}
    for c in completions:
        by_status[c.status] = by_status.get(c.status, 0) + 1
    health = engine.health_report()
    print("[serve] statuses: " +
          " ".join(f"{k}={v}" for k, v in sorted(by_status.items())))
    print("[serve] health: " +
          " ".join(f"{k}={v}" for k, v in health.items() if v))
    if args.restore:
        print(f"[serve] recovered via {args.restore}: "
              f"recoveries={health.get('recoveries', 0)} "
              f"replayed_tokens={health.get('replayed_tokens', 0)}")
    if sched.cache_layout == "paged":
        stats = sched.prefix_stats()
        print(f"[serve] prefix store: {stats['hits']} hits / "
              f"{stats['misses']} misses | {stats['shared_tokens']} prompt "
              f"tokens served from shared pages (zero prefill FLOPs)")
    spec = sched.spec_stats()
    if spec:
        print(f"[serve] speculative: {spec['emitted_tokens']} tokens over "
              f"{spec['verify_windows']} verify windows "
              f"({spec['tokens_per_window']:.2f} tok/window, "
              f"draft acceptance {spec['acceptance_rate']:.2f})")
    for c in completions[:2]:
        print(f"  req{c.rid}: prompt_len={c.prompt_len} "
              f"finished_by={c.finished_by} -> {c.tokens}")
    if args.strict:
        bad = sorted({c.status for c in completions if c.status != "ok"})
        if bad:
            print(f"[serve] --strict: non-ok terminal statuses {bad}")
            raise SystemExit(1)
    return completions


def main():
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    help="architecture preset to serve")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--requests", type=int, default=4,
                    help="number of synthetic requests to serve")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="tokens per synthetic prompt")
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens to generate per request")
    ap.add_argument("--fp", action="store_true",
                    help="serve in bf16 instead of int8 (baseline)")
    ap.add_argument("--no-kv-int8", action="store_true",
                    help="keep the KV cache bf16 (kv ablation)")
    ap.add_argument("--kv-bits", type=int, default=8, choices=[8, 4],
                    help="quantized KV cache width: 8 (int8) or 4 (packed "
                         "int4 nibbles — quarter of the bf16 cache bytes)")
    ap.add_argument("--finetune-thresholds", type=int, default=0,
                    help="train the KV quantization thresholds by "
                         "distillation for N epochs (<= 8) before freezing "
                         "them (paper §3); 0 = static §2 calibration only")
    ap.add_argument("--loop", action="store_true",
                    help="legacy per-token Python loop (vs lax.scan)")
    ap.add_argument("--pallas", action="store_true", default=None,
                    help="fused Pallas kernels (prefill + decode attention, "
                         "int8 matmul); default: on for TPU backends, off "
                         "on CPU where interpret mode is emulation-slow")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked ragged prefill: scan fixed-size prompt "
                         "chunks with a per-request length vector (one "
                         "executable for every prompt length)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for sampled decoding")
    ap.add_argument("--strategy", default=None,
                    choices=["greedy", "sample", "speculative"],
                    help="decode strategy (launch/strategies.py); default "
                         "auto-picks sample when --temperature > 0, else "
                         "greedy.  speculative drafts --spec-k tokens by "
                         "prompt-lookup n-gram matching and verifies them "
                         "in ONE batched pass over the int8 cache — "
                         "bit-identical tokens to greedy, fewer decode "
                         "dispatches when the text repeats")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative draft-window length (tokens drafted "
                         "per verify pass)")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup n-gram size for speculative "
                         "drafting")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="continuous batching: serve --requests ragged "
                         "requests through N cache slots with streaming "
                         "admission (launch/scheduler.py)")
    ap.add_argument("--block-steps", type=int, default=8,
                    help="scheduler decode-block length (admission happens "
                         "at block boundaries)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="EOS token id for the scheduler (< 0 disables; a "
                         "slot stops generating when it emits this)")
    ap.add_argument("--cache-layout", default="ring",
                    choices=["dense", "ring", "paged"],
                    help="KV-cache layout (repro.cache): ring = SWA layers "
                         "ring-buffered, rest dense (default); dense = "
                         "absolute slots everywhere; paged = page pool + "
                         "block tables (enables prompt prefix sharing "
                         "under --max-slots)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per page for --cache-layout paged")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request completion deadline in ms (scheduler "
                         "path): requests that miss it retire with status "
                         "'timeout' at the next block boundary")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue for the scheduler: at "
                         "most N requests waiting (default: unbounded)")
    ap.add_argument("--shed-policy", default="shed",
                    choices=["shed", "block"],
                    help="what a full admission queue does with new "
                         "arrivals: shed = retire them immediately with "
                         "status 'shed'; block = hold them out until the "
                         "queue drains")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection (launch/faults.py): "
                         "inline JSON or a path to a JSON file, e.g. "
                         "'{\"reject\": [2], \"nan_decode\": [[3, 1]]}'")
    ap.add_argument("--journal", default=None,
                    help="write-ahead request journal path (scheduler "
                         "path): every admission / block boundary / "
                         "retirement is journaled, so a crashed run can "
                         "restart with --restore journal and finish "
                         "bit-identically (launch/journal.py)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="write a full serving-state snapshot every N "
                         "decode-block boundaries through the checkpoint "
                         "manager (0 = off; needs --snapshot-dir)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="checkpoint directory for serving-state "
                         "snapshots (enables --restore snapshot)")
    ap.add_argument("--restore", default=None,
                    choices=["journal", "snapshot"],
                    help="recover a crashed run instead of serving fresh "
                         "requests: journal = replay the --journal file "
                         "and rebuild in-flight state via the resume "
                         "prefill; snapshot = restore the newest "
                         "--snapshot-dir checkpoint and continue decoding")
    ap.add_argument("--strict", action="store_true",
                    help="exit with code 1 if any request retires with a "
                         "non-'ok' terminal status (CI gating)")
    ap.add_argument("--spans", default=None,
                    help="write the scheduler's span records of the run "
                         "(phases of the host loop, one timeline per "
                         "request; launch/telemetry.py) to PATH as JSON "
                         "lines when it ends, a crashed run included")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained params from a launch/train.py "
                         "checkpoint directory (default: random init)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard count: projections split "
                         "across N devices, row epilogues all-reduce int32 "
                         "accumulators (requires int8 mode; token-identical "
                         "to --tp 1)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel shard count: the KV cache's "
                         "sequence axis splits across N devices, decode "
                         "merges per-shard flash partials exactly (dense/"
                         "ring cache layouts; token-identical to --sp 1)")
    ap.add_argument("--mesh", default="auto", choices=["auto", "dryrun"],
                    help="sharded-serving mesh mode: auto = build "
                         "make_serving_mesh(max(tp, sp)) and serve; dryrun "
                         "= compile the sharded prefill/decode executables, "
                         "print the HLO collective audit (int8-on-the-wire "
                         "assertion) and exit without serving")
    args = ap.parse_args()
    if (args.tp > 1 or args.sp > 1) and args.fp:
        ap.error("--tp/--sp shard the int8 engine (--fp has no integer "
                 "accumulators to reduce exactly)")
    if args.mesh == "dryrun" and args.tp <= 1 and args.sp <= 1:
        ap.error("--mesh dryrun audits the sharded executables — give it "
                 "--tp N or --sp N")
    if (args.journal or args.snapshot_dir or args.restore
            or args.strict or args.spans) and not args.max_slots:
        ap.error("--journal/--snapshot-dir/--restore/--strict/--spans need "
                 "--max-slots (the continuous-batching scheduler)")
    if args.restore == "journal" and not args.journal:
        ap.error("--restore journal needs --journal PATH")
    if args.restore == "snapshot" and not args.snapshot_dir:
        ap.error("--restore snapshot needs --snapshot-dir DIR")

    fault_plan = args.fault_plan
    if fault_plan is not None:
        from repro.launch.faults import FaultPlan
        fault_plan = FaultPlan.parse(fault_plan)
        if args.restore == "snapshot" and fault_plan.crash:
            # a snapshot may predate the crash boundary, so the restored
            # run would reach it and crash again on every restart;
            # journal replay resumes AT the boundary, so its plan keeps
            # later crash points live for multi-crash chains
            fault_plan = dataclasses.replace(fault_plan, crash=())

    use_pallas = (jax.default_backend() == "tpu" if args.pallas is None
                  else args.pallas)
    sharded = args.tp > 1 or args.sp > 1
    engine_cls = Engine
    shard_kw = {}
    if sharded:
        from repro.shard.engine import ShardedEngine

        engine_cls = ShardedEngine
        shard_kw = dict(tp=args.tp, sp=args.sp)
    engine = engine_cls.from_checkpoint(
        args.arch, checkpoint_dir=args.ckpt_dir, smoke=args.smoke,
        **shard_kw,
        fp=args.fp, kv_int8=not args.no_kv_int8, kv_bits=args.kv_bits,
        finetune_thresholds=args.finetune_thresholds, use_pallas=use_pallas,
        calib_batch=args.requests, calib_len=args.prompt_len,
        cache_layout=args.cache_layout, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk, temperature=args.temperature,
        top_p=args.top_p, seed=args.seed, decode_strategy=args.strategy,
        spec_k=args.spec_k, spec_ngram=args.spec_ngram,
        queue_cap=args.queue_cap, shed_policy=args.shed_policy,
        fault_plan=fault_plan, journal=args.journal,
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir)
    if not args.fp:
        print(f"[serve] converted: {engine.n_int8_weights()} int8 weight "
              "tensors resident")
    if sharded:
        print(f"[serve] sharded serving: tp={args.tp} sp={args.sp} over "
              f"{max(args.tp, args.sp)} devices")
        if args.mesh == "dryrun":
            import json

            report = engine.dry_run_report(batch=args.requests,
                                           prompt_len=args.prompt_len)
            print(json.dumps(report, indent=2, default=str))
            verdict = report["int8_all_reduces_ok"]
            print(f"[serve] dryrun: int8_all_reduces_ok={verdict}")
            raise SystemExit(0 if verdict else 1)

    if args.max_slots:
        return run_continuous(args, engine)

    # batched requests from the pipeline (prompt = first prompt_len tokens)
    spec = DP.spec_for(engine.cfg, ShapeSpec("cli", "train",
                                             args.prompt_len, args.requests))
    batch = DP.make_batch(spec, 12345)
    batch.pop("labels", None)
    if not args.no_kv_int8:
        # shape-only: count int8 KV leaves without allocating a cache
        abstract = jax.eval_shape(
            lambda: engine.init_cache(args.requests,
                                      engine._cache_len(args.prompt_len,
                                                        args.gen)))
        n_kv8 = sum(1 for l in jax.tree.leaves(abstract)
                    if l.dtype == jnp.int8)
        kind = ("packed-int4" if engine.policy.kv_bits == 4 else "int8")
        print(f"[serve] kv cache: {n_kv8} {kind} KV tensors resident "
              f"({engine.cache_layout} layout)")

    res = engine.generate_batch(batch, args.gen,
                                prompt_len=args.prompt_len, loop=args.loop)
    kind = "loop" if args.loop else "scan"
    pf_kind = (f"chunked/{args.prefill_chunk}" if args.prefill_chunk
               else "one-shot")
    pf_tps = res.prompt_tokens / max(res.prefill_s, 1e-9)
    print(f"[serve] {args.requests} requests | prefill ({pf_kind}) "
          f"{res.prefill_s*1e3:.1f} ms ({pf_tps:.0f} tok/s) "
          f"| {args.gen} tokens ({kind}) in {res.decode_s*1e3:.1f} ms "
          f"({res.decode_s/max(args.gen-1,1)*1e3:.1f} ms/tok)")
    out = res.tokens
    for r in range(min(args.requests, 2)):
        print(f"  req{r}: prompt={batch['tokens'][r, :8].tolist()}... "
              f"-> generated={out[r].tolist()}")
    return out


if __name__ == "__main__":
    main()
