#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
and a traffic mix; everything else follows from those files.  One run:

  set-up    the weights from the seed (one jitted call), the program's
            calibration and int8 conversion, the slot scheduler at the
            mix's slots and caps, and one admission and one decode block
            to compile (or load from the compile cache) every program the
            window drives.  All of it is ``setup_s``.
  window    ``SlotScheduler.run`` over the mix's requests: served to their
            end ("drain") or stopped after ``--seconds`` ("cut").  The
            harness watches it through ``bench/hooks.py``; with
            ``--trace 1`` a part of it is profiled.
  check     after the window, with the program's state freed: the served
            tokens of a sample of finished requests against the float32
            reference (``bench/check.py``), no failed request, nothing
            compiled inside the window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
A run on anything but a chip in ``bench/peaks.py`` exits 2 with no
result.

``--control`` serves at int4 weights and activations (the program's own
``bits=4`` path) instead of the configuration's int8: the control that the
correctness check must fail.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import arch, check, hooks, measure, traffic  # noqa: E402
from bench import weights as W  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
TRACE_S = 1.5          # seconds profiled in a --trace 1 run, unless the
                       # mix sets its own ``trace_s``
WARM_RID = 10**9       # the warm-up request; traffic rids count from 0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple:
    """(workload entry, configuration file, traffic mix, BENCHMARK.json)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    arch.of(cfg)        # a configuration names its architecture module
    return cell, cfg, traffic.load(cell["traffic"]), spec


def import_jax():
    """JAX, with the TPU runtime's logs kept inside the checkout."""
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    hooks.import_program()
    import jax
    return jax


def device_info(jax, chips: int) -> dict:
    """The device as JAX reports it; exits 2 unless it is a chip with
    published peaks and there are enough of them."""
    from bench import peaks

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX sees {devs[0].platform} ({kind})")
        raise SystemExit(2)
    try:
        peaks.for_kind(kind)
    except KeyError as e:
        log(str(e))
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX sees {len(devs)}")
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def metric_file(name: str) -> Path:
    """``bench/metrics/<name>.py``, else the reader of the name's stem: a
    quantity reported per cell (``admit_ms.chat``, ``admit_ms.batch``)
    shares the one reader ``admit_ms.py`` unless a cell's name has its
    own file."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = ROOT / "bench" / "metrics" / (".".join(parts[:k]) + ".py")
        if path.is_file():
            return path
    raise SystemExit(f"bench: no reader for metric {name!r} in "
                     f"bench/metrics/")


def metric_reader(name: str):
    path = metric_file(name)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: dict, traced: bool) -> list:
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


class CompileCounter:
    """Executables JAX builds or loads, counted from its own monitoring
    events: one per jit-cache miss, compiled or read from the cache."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.n = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, _secs, **_kw):
            if name == event:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def set_up(cfg: dict, mix: dict, seed: int):
    """Weights, engine and scheduler, warmed on one admission and one
    decode block.  Returns (engine, scheduler, server shape)."""
    import jax
    import numpy as np

    t = time.monotonic()
    weights = W.make(cfg, seed)
    jax.block_until_ready(weights)
    log(f"set-up: weights {time.monotonic() - t:.3f} s")
    t = time.monotonic()
    engine = hooks.build_engine(cfg, weights)
    del weights
    jax.block_until_ready(engine.serve_params)
    log(f"set-up: calibration + int8 conversion {time.monotonic() - t:.3f} s")
    t = time.monotonic()
    srv = mix["server"]
    sched = hooks.make_scheduler(engine, srv)
    warm = hooks.make_request(WARM_RID, np.zeros(srv["prompt_cap"], np.int32),
                              srv["block_steps"] + 1, 0.0)
    sched.run([warm])
    log(f"set-up: scheduler + warm-up (compile or cache load) "
        f"{time.monotonic() - t:.3f} s; executables "
        f"{hooks.executable_counts(sched)}")
    server = dict(srv, prompt_cap=hooks.padded_prompt_cap(sched),
                  chunk=hooks.prefill_chunk(sched),
                  cache_len=hooks.cache_len(sched))
    return engine, sched, server


def serve(sched, mix: dict, requests: list, seconds: float,
          trace: bool) -> hooks.Recorder:
    """The measured window."""
    reqs = [hooks.make_request(rid, toks, budget, due)
            for rid, toks, budget, due in requests]
    tw = {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # a v5e trace holds about a million device events a second here
        tw = dict(trace_dir=str(TRACE_DIR), trace_from_s=0.3 * seconds,
                  trace_s=min(mix.get("trace_s", TRACE_S), 0.3 * seconds))
    rec = hooks.Recorder(sched, cut_s=seconds if mix["window"] == "cut"
                         else None, **tw)
    rec.install()
    rec.outcomes = rec.run(reqs)
    return rec


def host_stalls(rec: hooks.Recorder) -> str:
    """Where the window lost time on the host: the longest gap between
    one span's end and the next one's start, the longest admission and
    block, and the garbage collections."""
    spans = sorted(rec.admits + rec.blocks, key=lambda x: x.t0)
    t0 = rec.t_start or 0.0
    parts = []
    if len(spans) > 1:
        gap, at = max((b.t0 - a.t1, a.t1) for a, b in zip(spans, spans[1:]))
        parts.append(f"longest gap between spans {1e3 * gap:.1f} ms at "
                     f"{at - t0:.3f} s")
    for name, items in (("admission", rec.admits), ("block", rec.blocks)):
        if items:
            x = max(items, key=lambda x: x.t1 - x.t0)
            parts.append(f"longest {name} {1e3 * (x.t1 - x.t0):.1f} ms at "
                         f"{x.t0 - t0:.3f} s")
    if rec.gc_pauses:
        start, secs, gen = max(rec.gc_pauses, key=lambda p: p[1])
        parts.append(f"{len(rec.gc_pauses)} garbage collections, "
                     f"{1e3 * sum(p[1] for p in rec.gc_pauses):.1f} ms in "
                     f"all, longest {1e3 * secs:.1f} ms (generation {gen}) "
                     f"at {start - t0:.3f} s")
    gap, at = rec.watch_gap
    parts.append(f"the 50 ms watcher's longest sleep {1e3 * gap:.1f} ms at "
                 f"{at - t0:.3f} s")
    for st in sorted(rec.stalls, key=lambda x: -x.seconds)[:3]:
        where = " < ".join(reversed(st.stack)) or "no frame taken"
        parts.append(f"stall: {st.kind} of {st.seconds:.3f} s at "
                     f"{st.t0 - t0:.3f} s, process CPU {st.process_s:.2f} s, "
                     f"main thread in {where}")
    return "; ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve int4 weights and activations (the "
                    "correctness check's control)")
    args = ap.parse_args(argv)

    cell, cfg, mix, spec = load_cell(args.workload)
    if args.control:
        cfg = dict(cfg, weight_bits=4)
    jax = import_jax()
    device = device_info(jax, cell["chips"])
    log(f"device {device}; compile cache {hooks.enable_compile_cache()}")
    result = run_cell(jax, cell, cfg, mix, spec, args.seed, args.seconds,
                      bool(args.trace), device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


def run_cell(jax, cell, cfg, mix, spec, seed, seconds, traced,
             device) -> dict:
    """Set-up, window, metrics and check of one run; the result line."""
    from bench import peaks

    counter = CompileCounter()
    engine, sched, server = set_up(cfg, mix, seed)
    requests = traffic.generate(mix, cfg["vocab_size"], seconds, seed)
    counts0, compiles0 = hooks.executable_counts(sched), counter.n
    setup_s = time.monotonic() - T_PROCESS
    rec = serve(sched, mix, requests, seconds, traced)
    compiles = counter.n - compiles0
    counts1 = hooks.executable_counts(sched)
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    log(f"window: {len(rec.admits)} admissions, {len(rec.blocks)} decode "
        f"blocks, {len(rec.outcomes)} requests ended; executables "
        f"{counts1}; memory peak {device['memory_peak_bytes']}")
    log(f"host: {host_stalls(rec)}")

    # the program's state goes before the reference runs
    rec.sched = None
    del engine, sched
    gc.collect()

    run = measure.Run(cell=cell, cfg=cfg, mix=mix, seconds=seconds,
                      setup_s=setup_s,
                      peaks=peaks.PEAKS.get(device["kind"], {}),
                      requests=requests, rec=rec, outcomes=rec.outcomes,
                      server=server)
    out = {}
    if traced:
        from bench import trace
        t = time.monotonic()
        run.trace = trace.reduce(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace: {len(run.trace.spans)} spans, "
            f"{sum(len(s) for s, _e in run.trace.ops.values())} device "
            f"events, reduced in {time.monotonic() - t:.3f} s")
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown()
    metrics = {}
    for m in cell_metrics(spec, cell, traced):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    ok = [o for o in rec.outcomes if o.status == "ok"]
    failed = [o for o in rec.outcomes if o.status != "ok"]
    prompts = {rid: toks for rid, toks, _b, _d in requests}
    t = time.monotonic()
    picked = check.sample(ok, seed)
    gap, n_tok = (check.logit_gap(cfg, seed, picked, prompts,
                                  server["prompt_cap"] + server["gen_cap"])
                  if picked else (math.inf, 0))
    log(f"check: {n_tok} served tokens of {len(picked)} requests against "
        f"the reference in {time.monotonic() - t:.3f} s")
    limit = cfg["limits"]["logit_gap"]
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "compiles_in_window": {
            "value": compiles + sum(counts1.values())
            - sum(counts0.values()), "limit": 0},
    }
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    # a cut window attempted what it admitted (or refused); the rest of
    # a backlog was never tried
    attempted = (len({a.rid for a in rec.admits}
                     | {o.rid for o in rec.outcomes})
                 if mix["window"] == "cut" else len(requests))
    return {"correct": correct, "attempted": attempted,
            "failed": len(failed), "metrics": metrics, "device": device,
            **out, "checks": checks}


if __name__ == "__main__":
    sys.exit(main())
