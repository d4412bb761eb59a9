"""The architecture modules (``bench/arch/``).

The llama module reads exactly what the harness read before the
architecture moved out of ``weights.py``, ``reference.py``, ``hooks.py``,
``measure.py`` and ``kernels/quant_matmul.py``: the golden values below
were taken from that harness.  A configuration must name its module, and
a new architecture needs only its module and its configuration file.
"""
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import arch, hooks, measure, reference, trace, weights

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# sha256 of each leaf's bytes of weights.make(tiny, 3)
ONES = "2f20cd03c9cd392a406c56232b0ff93a15f6d6d7da79086bfa14f55d4a4031b0"
WEIGHTS = {
    "['embed']": "9f52784aff2d290812ab1ee55366affb98f334070405c5b87e0168f0e8a9fdb8",
    "['final_norm']": ONES,
    "['layers'][0]['attn_norm']": ONES,
    "['layers'][0]['mlp_norm']": ONES,
    "['layers'][0]['w_down']": "b910c6e86fc20436fa9e4fa1aef9bf28d275d9fce99c00adfee5df43b2e5d253",
    "['layers'][0]['w_gate']": "61945495a65ba843e76a5ff81f953170f6d3be99cf25ea066ab5a384b7410c25",
    "['layers'][0]['w_up']": "28403afc80642a0db4bea1688fe8793cc062b918245c4a3ab895be3f5ba2723f",
    "['layers'][0]['wk']": "37f747e7418cac449c538f051fec76f58bd6e872bcde8210e4e8ec0117ffb57e",
    "['layers'][0]['wo']": "fa37838723a9aade0a63bf14100c144bd47a38a378013f0185f3ac247a6cfc0a",
    "['layers'][0]['wq']": "a3dcc3fa92d2c51a43bc878c08bd11ee40aa9c04a10fac3947108c05334a75af",
    "['layers'][0]['wv']": "b88cf5c63d2f8b1159760f4d3f6edaa09ff6b58a86b3bc9306590c974599bc6f",
    "['layers'][1]['attn_norm']": ONES,
    "['layers'][1]['mlp_norm']": ONES,
    "['layers'][1]['w_down']": "132af77561fbe1b46dde9922af726d2b27407d9d96043772d0097b5dc74a8ca5",
    "['layers'][1]['w_gate']": "31bf463ed77a75be2a9a44f2e9abfd12386ce62be53e636c562d50c87f698f52",
    "['layers'][1]['w_up']": "c08011a0cf38ce9d3772405165603768a830fb9e40a9164d6fc4218dfa3eb0a4",
    "['layers'][1]['wk']": "7b38b8194b4096fce15102ef00aa52959c623cdd8a65dafea3e1967238b3a5bf",
    "['layers'][1]['wo']": "b220c108240fbc56fe2c1ccc32db1a1b7b6f87adcb212a9ac488032d0bd4122a",
    "['layers'][1]['wq']": "66c34a3e41a27b94b5394da5319c1620b3a8cc35dd90555b886dde98c68e0fc0",
    "['layers'][1]['wv']": "a685c34ffc73fd6cee2f54fd5a9c4ed7ba64890badd47c839dfec719ab5897ce",
}

# reference.gaps(tiny, 3, SAMPLE, [8, 12], rows=2, length=40)
GAPS = [
    [0.5718415379524231, 0.5743746161460876, 0.6587702035903931,
     0.30154865980148315, 0.3657025694847107, 0.3240033984184265,
     0.28194761276245117, 0.27209895849227905, 0.43207648396492004,
     0.4301963746547699, 0.4859088659286499, 0.7098103761672974],
    [0.29951754212379456, 0.5153387784957886, 0.5441679954528809,
     0.2528928518295288, 0.5374287962913513, 0.5532361268997192,
     0.4847257435321808, 0.32715266942977905, 0.36723029613494873,
     0.36408284306526184, 0.7235982418060303, 0.6043083667755127,
     0.3551837205886841, 0.7304772734642029, 0.5653275847434998,
     0.6026585102081299, 0.1578330099582672, 0.34931206703186035,
     0.4657753109931946, 0.37285327911376953, 0.7503658533096313],
]

MODEL_OPS = {
    "tiny": {"matmul": 147456, "readout": 32768, "per_key": 512},
    "smollm-135m": {"matmul": 212336640, "readout": 56623104,
                    "per_key": 69120},
    "granite-8b": {"matmul": 6979321856, "readout": 402653184,
                   "per_key": 262144},
}
PROJECTIONS = {
    "tiny": [(64, 64), (64, 32), (64, 32), (64, 64), (64, 128), (64, 128),
             (128, 64)],
    "smollm-135m": [(576, 576), (576, 192), (576, 192), (576, 576),
                    (576, 1536), (576, 1536), (1536, 576)],
    "granite-8b": [(4096, 4096), (4096, 1024), (4096, 1024), (4096, 4096),
                   (4096, 14336), (4096, 14336), (14336, 4096)],
}


def config(name):
    path = (DATA / "tiny.json" if name == "tiny"
            else ROOT / "bench" / "configs" / f"{name}.json")
    return json.loads(path.read_text())


def sample():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, size=20, dtype=np.int32),
            rng.integers(0, 256, size=33, dtype=np.int32)]


def leaf_hashes(w):
    return {jax.tree_util.keystr(p): hashlib.sha256(
                np.asarray(a).tobytes()).hexdigest()
            for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}


def assert_gaps_golden(g):
    assert len(g) == len(GAPS)
    for got, want in zip(g, GAPS):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, np.asarray(want, np.float32))


def test_weights_golden():
    assert leaf_hashes(weights.make(config("tiny"), 3)) == WEIGHTS


def test_reference_gaps_golden():
    g = reference.gaps(config("tiny"), 3, sample(), [8, 12], rows=2,
                       length=40)
    assert_gaps_golden(g)


@pytest.mark.parametrize("name", sorted(MODEL_OPS))
def test_op_counts_golden(name):
    cfg = config(name)
    mod = arch.of(cfg)
    assert mod.model_ops(cfg) == MODEL_OPS[name]
    assert mod.projections(cfg) == PROJECTIONS[name]


@pytest.mark.parametrize("cfg", [{"name": "x"}, {"name": "x", "arch": "nope"},
                                 {"name": "x", "arch": "../hooks"},
                                 {"name": "x", "arch": ""}],
                         ids=["missing", "unknown", "path", "empty"])
def test_configuration_must_name_a_module(cfg):
    with pytest.raises(SystemExit, match="bench/arch"):
        arch.of(cfg)
    tiny = {k: v for k, v in config("tiny").items() if k != "arch"}
    with pytest.raises(SystemExit, match="bench/arch"):
        weights.make(dict(tiny, **cfg), 3)


CONTRACT = ("layer_params", "reference_layer", "program_config",
            "program_params", "norm_count", "model_ops", "projections")


def qm_decode_roofline(cfg):
    """``quant_matmul_roofline.decode`` over one traced decode block with
    10 us of quant_matmul events."""
    red = trace.Reduction(
        ops={"quant_matmul": (np.array([100, 6000]), np.array([5100, 11000]))},
        busy=(np.array([100]), np.array([11000])),
        spans={("decode", 1): (0, 20000), ("window", 0): (0, 20000)},
        window=(0, 20000), busy_s=10.9e-6, window_s=20e-6)
    block = hooks.Block(1, 1.0, 2.0, [(0, 1, 8, 4)], {0: 4})
    rec = SimpleNamespace(admits=[], blocks=[block], trace_window=(0.5, 3.0))
    run = measure.Run(None, cfg, None, 2.0, 0, {"int8_ops": 393e12,
                                               "hbm_bytes_per_s": 819e9},
                      [], rec, [], {"max_slots": 4, "block_steps": 8},
                      trace=red)
    return measure.roofline_pct(run, "quant_matmul", "decode")


def test_new_architecture_from_its_files_alone(tmp_path, monkeypatch):
    """A second module (a copy of llama.py under another name) in a
    directory of its own, and a configuration naming it: weights, engine,
    reference and readers run on it, and each finds it, with no other
    file touched.  Being a copy, it reads what llama reads."""
    src = (arch.DIR / "llama.py").read_text()
    (tmp_path / "llama_copy.py").write_text(src + '''
SEEN = set()


def _seen(f):
    def g(*a, **kw):
        SEEN.add(f.__name__)
        return f(*a, **kw)
    return g


for _n in ("layer_params", "reference_layer", "program_config",
           "program_params", "norm_count", "model_ops", "projections"):
    globals()[_n] = _seen(globals()[_n])
''')
    tiny = config("tiny")
    cfg = dict(tiny, arch="llama_copy")
    want_roofline = qm_decode_roofline(tiny)
    monkeypatch.setattr(arch, "DIR", tmp_path)
    mod = arch.of(cfg)
    assert Path(mod.__file__) == tmp_path / "llama_copy.py"

    w = weights.make(cfg, 3)
    assert leaf_hashes(w) == WEIGHTS
    engine = hooks.build_engine(cfg, w)
    assert any(x.dtype == np.int8
               for x in jax.tree.leaves(engine.serve_params))
    assert_gaps_golden(reference.gaps(cfg, 3, sample(), [8, 12], rows=2,
                                      length=40))
    assert mod.model_ops(cfg) == MODEL_OPS["tiny"]
    got = qm_decode_roofline(cfg)
    assert got is not None and 0 < got <= 100
    assert got == want_roofline
    assert mod.SEEN == set(CONTRACT)
    assert all(f"``{name}(" in arch.__doc__ for name in CONTRACT)
    # the harness's own directory was not consulted
    with pytest.raises(SystemExit):
        arch.load("llama")
