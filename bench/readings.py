#!/usr/bin/env python3
"""The readings a correctness limit is set from: a cell's compared number
on many seeds, for the program as configured and for the control, in one
process so that set-up compiles once.

    python3 bench/readings.py --workload smollm-135m.chat --seconds 8 \
        --seeds 101,102,... --control-seeds 201,202,203

Each seed runs the cell's own traffic for ``--seconds`` (served to its end
or cut, as the mix says), then the same check a benchmark run makes.  One
JSON line per seed.  Not part of a benchmark run; see PERF.md for the
readings and the limits set from them.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, hooks, run, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    cell, cfg, mix, _spec = run.load_cell(args.workload)
    jax = run.import_jax()
    run.device_info(jax, cell["chips"])
    hooks.enable_compile_cache()
    jobs = [(int(s), False) for s in args.seeds.split(",") if s] + [
        (int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in jobs:
        c = dict(cfg, weight_bits=4) if control else cfg
        engine, sched, server = run.set_up(c, mix, seed)
        requests = traffic.generate(mix, cfg["vocab_size"], args.seconds,
                                    seed)
        rec = run.serve(sched, mix, requests, args.seconds, False)
        rec.sched = None
        del engine, sched
        gc.collect()
        ok = [o for o in rec.outcomes if o.status == "ok"]
        picked = check.sample(ok, seed)
        prompts = {rid: toks for rid, toks, _b, _d in requests}
        gap, n_tok = (check.logit_gap(
            cfg, seed, picked, prompts,
            server["prompt_cap"] + server["gen_cap"]) if picked
            else (math.inf, 0))
        print(json.dumps({"seed": seed, "control": control, "gap": gap,
                          "tokens": n_tok, "requests": len(picked),
                          "finished": len(ok),
                          "failed": len(rec.outcomes) - len(ok)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
