"""One module per model architecture, found by the configuration's name.

A configuration file names its architecture under ``"arch"``; the
harness loads ``bench/arch/<arch>.py`` by path, as it finds the readers
under ``bench/metrics/`` and the counts under ``bench/kernels/``.  A new
architecture is a new file here, with no other file of the harness
edited.  Every architecture module defines:

``layer_params(cfg, key, i, dtype) -> dict``
    Layer ``i``'s weights, made from ``jax.random.fold_in(key, 1 + i)``
    and nothing else, so that one layer can be made again alone, bit for
    bit (the reference does so layer by layer).  Traceable in ``i``: one
    compiled program makes every layer.  Taking the index lets layer
    kinds differ by depth.  The embedding, the final norm and the tied
    readout are shared (``bench/weights.py``, ``bench/reference.py``).
``reference_layer(cfg, lw, x) -> x``
    One layer of the plain float32 forward over one sequence ``x``
    (S, d), from position 0, on the leaves ``layer_params`` made (cast to
    float32).  It imports nothing of the program; ``bench/reference.py``
    keeps the helpers ``_rms`` and ``_rope``.
``program_config(cfg)``, ``program_params(weights)``, ``norm_count(cfg)``
    The mapping onto the program: its ``ModelConfig``, its parameter tree
    for the weights ``bench/weights.py:make`` returns, and how many
    RMSNorms the built model holds (``bench/hooks.py:build_engine`` sets
    each one's eps and checks the count).  Raise ``ValueError`` for a
    configuration the program cannot serve.
``model_ops(cfg) -> {"matmul", "readout", "per_key"}``
    Operations of one token through the model: the matrix products that
    are active for a token (2 per weight), the readout, and per key
    attended the score and value products, over all layers.
``projections(cfg) -> [(K, N), ...]``
    The calls ``quant_matmul`` is handed per layer, one entry per call.
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

DIR = Path(__file__).resolve().parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_-]*")


def load(name: str):
    """``<DIR>/<name>.py``, loaded by path; stops the run if it is not
    there."""
    path = DIR / f"{name}.py"
    if not (isinstance(name, str) and NAME.fullmatch(name)
            and path.is_file()):
        raise SystemExit(f"bench: no architecture module {name!r} in {DIR}/")
    return _load(path)


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_arch_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cfg: dict):
    """The architecture module the configuration names."""
    if "arch" not in cfg:
        raise SystemExit(f"bench: configuration {cfg.get('name')!r} names "
                         f"no \"arch\"; its module goes in {DIR}/")
    return load(cfg["arch"])
