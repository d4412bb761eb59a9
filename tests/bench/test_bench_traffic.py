"""The traffic generator: deterministic per seed, clipped, and the same
work for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(p.stem for p in (Path(traffic.MIXES)).glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_mix_deterministic_clipped_same_work(name):
    mix = traffic.load(name)
    a = traffic.generate(mix, 49152, 30.0, 2**31 + 17)
    b = traffic.generate(mix, 49152, 30.0, 2**31 + 17)
    c = traffic.generate(mix, 49152, 30.0, 5)
    assert len(a) == len(b) == len(c) > 0
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[2:] == y[2:]
        np.testing.assert_array_equal(x[1], y[1])
    for rid, toks, budget, due in a:
        assert mix["prompt"]["min"] <= len(toks) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= budget <= mix["output"]["max"]
        assert toks.dtype == np.int32 and toks.min() >= 0 and toks.max() < 49152
    # another seed: another order and other tokens, the same lengths
    assert sorted(len(x[1]) for x in a) == sorted(len(x[1]) for x in c)
    assert sorted(x[2] for x in a) == sorted(x[2] for x in c)
    assert [len(x[1]) for x in a] != [len(x[1]) for x in c]
    dues = [x[3] for x in a]
    assert dues == sorted(dues) and dues[0] == 0.0


def test_poisson_rate_and_strata():
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 8.0},
           "prompt": {"median": 256, "sigma": 1.0, "min": 16, "max": 1024},
           "output": {"median": 128, "sigma": 0.8, "min": 8, "max": 512}}
    reqs = traffic.generate(mix, 100, 40.0, 3)
    assert len(reqs) == 320
    span_s = reqs[-1][3] / 1e3
    assert 36.0 < span_s < 41.0          # about n / rate
    # every block of STRATA requests holds the same set of lengths
    lens = [len(r[1]) for r in reqs]
    s = traffic.STRATA
    assert sorted(lens[:s]) == sorted(lens[s:2 * s])
    assert np.median(lens) == pytest.approx(256, rel=0.1)


def test_backlog_all_due_at_zero():
    mix = traffic.load("decode-heavy")
    reqs = traffic.generate(mix, 49152, 30.0, 1)
    assert len(reqs) == mix["arrival"]["count"]
    assert all(r[3] == 0.0 for r in reqs)


def test_every_mix_file_is_json_with_server():
    for name in MIXES:
        mix = json.loads((Path(traffic.MIXES) / f"{name}.json").read_text())
        assert {"arrival", "prompt", "output", "server", "window"} <= set(mix)
        assert mix["window"] in ("drain", "cut")
