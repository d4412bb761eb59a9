#!/usr/bin/env python3
"""Find a Poisson cell's knee: one engine, one process, several rates.

    python3 bench/sweep.py --workload smollm-135m.chat --seed 5 \
        --seconds 20 --rates 4,6,8,10

For each rate the cell's mix is served for ``--seconds`` at that rate and
stopped there.  A rate is sustained when the queue of requests due but
not yet admitted does not grow over the window: the printed ``pending``
(due and not admitted at the cut) stays a few requests, and the median
wait for admission of the window's last third stays near its first
third's.  The knee is the highest such rate; the cell's mix then offers
0.8 of it.  Not part of a benchmark run: the knee is found once, when a
cell is defined, and recorded in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import hooks, measure, run, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args()
    cell, cfg, mix, _spec = run.load_cell(args.workload)
    jax = run.import_jax()
    run.device_info(jax, cell["chips"])
    hooks.enable_compile_cache()
    _engine, sched, _server = run.set_up(cfg, mix, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrival={"kind": "poisson", "rate_per_s": rate})
        reqs = traffic.generate(m, cfg["vocab_size"], args.seconds,
                                args.seed)
        rec = hooks.Recorder(sched, cut_s=args.seconds)
        rec.install()
        t = time.monotonic()
        rec.run([hooks.make_request(rid, toks, budget, due)
                 for rid, toks, budget, due in reqs])
        # unhook: the next rate installs a fresh recorder
        for name in ("_admit", "_decode"):
            sched.__dict__.pop(name, None)
        due = {rid: d for rid, _t, _b, d in reqs}
        waits = [(due[a.rid], 1e3 * (a.t0 - rec.t_start) - due[a.rid])
                 for a in rec.admits]
        third = args.seconds * 1e3 / 3
        first = [w for d, w in waits if d < third]
        last = [w for d, w in waits if d >= 2 * third]
        ttft = [1e3 * (a.t1 - rec.t_start) - due[a.rid] for a in rec.admits]
        out = {
            "rate_per_s": rate, "due": len(reqs),
            "admitted": len(rec.admits),
            "pending": sum(1 for d in due.values()
                           if d <= args.seconds * 1e3) - len(rec.admits),
            "wait_ms_first_third": statistics.median(first) if first
            else None,
            "wait_ms_last_third": statistics.median(last) if last else None,
            "ttft_p90_ms_admitted": measure.percentile(ttft, 0.9),
            "admit_ms": measure.mean_ms(rec.admits),
            "decode_block_ms": measure.mean_ms(rec.blocks),
            "wall_s": time.monotonic() - t,
            "host": run.host_stalls(rec),
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
