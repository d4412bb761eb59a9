"""The one traffic generator: a mix file in, requests out.

A mix is ``bench/traffic/<name>.json``:

    arrival   {"kind": "poisson", "rate_per_s": r}   open loop over the
              window: n = round(r * seconds) requests
              {"kind": "backlog", "count": n}          n requests, all due
              at t = 0
    prompt    {"median": m, "sigma": s, "min": a, "max": b}  lognormal
    output    the same, for the generated-token budget
    server    max_slots, prompt_cap, gen_cap, block_steps: the scheduler
              as the cell serves it
    window    "drain": serve every request due in the window to its end;
              "cut": stop at the window's end
    trace_s   (optional) seconds profiled in a ``--trace 1`` run

Every seed serves the same work.  Lengths are the lognormal's quantiles,
``STRATA`` at a time (each block of ``STRATA`` requests holds the same
set of lengths), and Poisson gaps are the exponential's quantiles; the
seed only shuffles them within each block and picks the tokens.  So two
seeds differ in order and content, not in how much there is to do.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIXES = Path(__file__).resolve().parent / "traffic"
STRATA = 32


def load(name: str) -> dict:
    path = MIXES / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic mix {path}")
    return json.loads(path.read_text())


def _quantiles(n: int, ppf) -> np.ndarray:
    """ppf at the midpoints of ``n`` equal-probability strata."""
    return np.asarray([ppf((i + 0.5) / n) for i in range(n)])


def _stratified(n: int, ppf, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws as blocks of ``STRATA`` quantiles (a last, shorter
    block of its own quantiles), each block shuffled: every seed gets the
    same values, in another order."""
    sizes = [STRATA] * (n // STRATA) + ([n % STRATA] if n % STRATA else [])
    return np.concatenate([rng.permutation(_quantiles(k, ppf))
                           for k in sizes])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal lengths, clipped to [min, max]."""
    z = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    x = _stratified(n, lambda p: math.exp(mu + sigma * z.inv_cdf(p)), rng)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def arrivals_ms(arrival: dict, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    if arrival["kind"] == "backlog":
        return np.zeros(int(arrival["count"]))
    if arrival["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    rate = float(arrival["rate_per_s"])
    n = max(1, round(rate * seconds))
    gaps = _stratified(n, lambda p: -math.log1p(-p), rng) / rate
    return (np.cumsum(gaps) - gaps[0]) * 1e3    # the first request at 0


def generate(mix: dict, vocab: int, seconds: float, seed: int) -> list:
    """[(rid, prompt tokens (int32), output budget, due ms)], by due time."""
    rng = np.random.default_rng(seed)
    due = arrivals_ms(mix["arrival"], seconds, rng)
    n = len(due)
    plen = lengths(mix["prompt"], n, rng)
    olen = lengths(mix["output"], n, rng)
    return [(i, rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32),
             int(olen[i]), float(due[i])) for i in range(n)]
