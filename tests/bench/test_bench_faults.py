"""The correctness check fails what it must.

A whole run of a cell at a small size on the CPU, past the harness's look
for a chip: sound, it is correct; served at int4 (the control), or with
the timed path broken underneath, it is not.  The faults a serving cell
on one chip can have: a decode step that returns the KV cache unchanged,
an admission whose prefilled cache is never spliced in, and a token
altered where the decode block produces it.
"""
import json
from pathlib import Path

import pytest

from bench import run

DATA = Path(__file__).resolve().parent / "data"
CELL = {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
        "chips": 1}
SPEC = {"end_to_end": [{"name": "ttft_p90_ms", "unit": "ms"},
                       {"name": "tpot_p90_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**31 + 101


def run_tiny(cfg=None):
    import jax

    cfg = cfg or json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    return run.run_cell(jax, CELL, cfg, mix, SPEC, SEED, 2.0, False, DEVICE)


def _unchanged_cache(make):
    def patched(*a, **kw):
        loop = make(*a, **kw)

        def broken(params, qparams, tok0, cache, *rest):
            out = loop(params, qparams, tok0, cache, *rest)
            return out[:2] + (cache,) + out[3:]
        return broken
    return patched


def _altered_token(make):
    def patched(*a, **kw):
        loop = make(*a, **kw)

        def broken(*args):
            out = loop(*args)
            return ((out[0] + 1) % 256,) + out[1:]
        return broken
    return patched


def _no_splice(insert):
    return lambda cache, slot_cache, slot: cache


FAULTS = {
    "decode_state_unchanged": ("repro.launch.strategies",
                               "make_strategy_slot_loop", _unchanged_cache),
    "decode_token_altered": ("repro.launch.strategies",
                             "make_strategy_slot_loop", _altered_token),
    "admission_not_spliced": ("repro.launch.scheduler",
                              "_slot_cache_insert", _no_splice),
}


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_int4_is_not_correct():
    cfg = json.loads((DATA / "tiny.json").read_text())
    res = run_tiny(dict(cfg, weight_bits=4))
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    import importlib

    module, name, wrap = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    res = run_tiny()
    assert not res["correct"], res["checks"]
