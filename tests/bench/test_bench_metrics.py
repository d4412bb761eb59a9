"""Metric readers on a hand-made record of a window."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

from bench import hooks, measure

METRICS = Path(__file__).resolve().parents[2] / "bench" / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_out_tok_s_counts_to_the_last_delivery():
    # two admissions, then two blocks of 4 steps; request 1's budget of 6
    # caps what the second block gives it; the last block ends at 2.5 s
    admits = [hooks.Admit(1, 0, 8, 0.0, 0.5), hooks.Admit(2, 1, 8, 0.5, 1.0)]
    blocks = [hooks.Block(3, 1.0, 1.5, [(0, 1, 100, 8), (1, 1, 6, 8)],
                          {0: 4, 1: 4}),
              hooks.Block(4, 2.0, 2.5, [(0, 5, 100, 12), (1, 5, 6, 12)],
                          {0: 4, 1: 4})]
    rec = SimpleNamespace(admits=admits, blocks=blocks, t_start=0.0)
    run = measure.Run(None, None, None, 2.0, 0, {}, [], rec, [], {})
    # request 0: 1 + 4 + 4 tokens, request 1: 1 + 4 + 1
    assert reader("out_tok_s")(run) == 15 / 2.5
    empty = SimpleNamespace(admits=[], blocks=[], t_start=0.0)
    assert reader("out_tok_s")(measure.Run(None, None, None, 2.0, 0, {}, [],
                                           empty, [], {})) is None
