"""The kernels' operation and byte counts against hand counts, at both
configurations' shapes."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import arch, measure

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def kernel(name):
    run = measure.Run(None, None, None, 0, 0, {}, [], None, [], {})
    return run.kernel(name)


def test_quant_matmul_hand_counts():
    qm = kernel("quant_matmul")
    # smollm decode, wq: 32 slots x 576 -> 576
    assert qm.cost(32, 576, 576) == (
        2 * 32 * 576 * 576,
        2 * 32 * 576 + 576 * 576 + 4 * 576 + 4 + 2 * 32 * 576)
    assert qm.cost(32, 576, 576) == (21233664, 407812)
    # granite decode, wk: 64 slots x 4096 -> 8 * 128
    assert qm.cost(64, 4096, 1024) == (536870912, 4853764)
    # the seven projections hold every matrix weight of a layer
    for name, per_layer in (("smollm-135m", 3538944),
                            ("granite-8b", 218103808)):
        calls = arch.load("llama").projections(cfg(name))
        assert sum(k * n for k, n in calls) == per_layer


def test_quant_matmul_calls_per_span():
    qm = kernel("quant_matmul")
    c = cfg("smollm-135m")
    run = SimpleNamespace(cfg=c, server={"chunk": 16, "prompt_cap": 1024,
                                         "max_slots": 32, "block_steps": 8})
    admit = list(qm.calls(run, "admit", None))
    decode = list(qm.calls(run, "decode", None))
    # 64 chunks x 30 layers of M=16; 8 steps x 30 layers of M=32
    assert admit[0] == tuple(x * 64 * 30 for x in qm.cost(16, 576, 576))
    assert decode[6] == tuple(x * 8 * 30 for x in qm.cost(32, 1536, 576))
    assert len(admit) == len(decode) == 7


def test_attention_hand_counts():
    da, pa = kernel("decode_attention"), kernel("prefill_attention")
    assert da.cost(100, 9, 3, 64) == (230400, 38400 + 4608)
    assert pa.cost(16, 16, 136, 9, 3, 64) == (4 * 136 * 9 * 64,
                                             2 * 16 * 3 * 64 + 8 * 16 * 9 * 64)
    c = cfg("smollm-135m")
    run = SimpleNamespace(cfg=c, server={"chunk": 16, "block_steps": 8})
    # a 20-token prompt: chunks of 16 and 4 rows, 210 causal pairs in all
    calls = list(pa.calls(run, "admit", SimpleNamespace(prompt_len=20)))
    per_pair = 4 * 9 * 64 * 30
    assert [ops for ops, _ in calls] == [136 * per_pair, 74 * per_pair]
    # two slots at positions 10 and 99, one step each of a block of 1
    run.server["block_steps"] = 1
    span = SimpleNamespace(slots=[(0, 1, 9, 10), (1, 5, 99, 99)])
    (ops, nbytes), = list(da.calls(run, "decode", span))
    assert ops == 4 * (11 + 100) * 9 * 64 * 30
    assert nbytes == (2 * (11 + 100) * 3 * 64 + 2 * 8 * 9 * 64) * 30


@pytest.mark.parametrize("name", ["smollm-135m", "granite-8b"])
def test_model_ops(name):
    c = cfg(name)
    mo = arch.of(c).model_ops(c)
    per_layer = {"smollm-135m": 3538944, "granite-8b": 218103808}[name]
    assert mo["matmul"] == 2 * per_layer * c["num_hidden_layers"]
    assert mo["readout"] == 2 * c["hidden_size"] * c["vocab_size"]
