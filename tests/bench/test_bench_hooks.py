"""bench/hooks.py on the program at a small size: the parameter tree it
builds is the program's, and its spans cover every admission and every
decode block of a scheduler run."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import arch, hooks, weights

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def cfg():
    return json.loads((DATA / "tiny.json").read_text())


@pytest.fixture(scope="module")
def engine(cfg):
    return hooks.build_engine(cfg, weights.make(cfg, 3))


def test_program_config_matches_file(cfg):
    llama = arch.load("llama")
    pcfg = llama.program_config(cfg)
    assert (pcfg.n_layers, pcfg.d_model, pcfg.n_heads, pcfg.n_kv_heads,
            pcfg.head_dim, pcfg.d_ff, pcfg.vocab) == (2, 64, 4, 2, 16, 128,
                                                     256)
    with pytest.raises(ValueError):
        llama.program_config(dict(cfg, tie_word_embeddings=False))


def test_norms_take_the_files_eps(cfg):
    """The program's ModelConfig has no eps; the hooks give every RMSNorm
    the file's, whatever it is."""
    from repro.models import build_model
    from repro.models.layers import RMSNorm

    llama = arch.load("llama")
    model = build_model(llama.program_config(cfg))
    assert hooks.set_norm_eps(model, 1e-5) == llama.norm_count(cfg)
    assert llama.norm_count(cfg) == 2 * cfg["num_hidden_layers"] + 1
    norms = [model.stack.final_norm] + [
        n for b in model.stack.blocks for n in (b.pre_norm, b.ffn_norm)]
    assert all(isinstance(n, RMSNorm) and n.eps == 1e-5 for n in norms)


def test_engine_serves_int8(engine):
    import jax.numpy as jnp
    import jax
    assert engine.policy.kv_int8 and engine.policy.kv_bits == 8
    assert any(x.dtype == jnp.int8 for x in jax.tree.leaves(engine.serve_params))


def test_spans_cover_every_admission_and_block(engine):
    server = {"max_slots": 3, "prompt_cap": 32, "gen_cap": 24,
              "block_steps": 4}
    sched = hooks.make_scheduler(engine, server)
    rng = np.random.default_rng(0)
    budgets = [5, 9, 3, 12, 7]
    reqs = [hooks.make_request(i, rng.integers(0, 256, size=8 + 3 * i,
                                               dtype=np.int32), b, 0.0)
            for i, b in enumerate(budgets)]
    rec = hooks.Recorder(sched)
    rec.install()
    outs = rec.run(reqs)
    assert sorted(a.rid for a in rec.admits) == list(range(5))
    assert len(rec.blocks) == sched.call_counts()["decode"] > 0
    assert [o.status for o in outs] == ["ok"] * 5
    assert {o.rid: len(o.tokens) for o in outs} == dict(enumerate(budgets))
    for a in rec.admits:
        assert a.t0 <= a.t1
    # the blocks' emissions, capped by each budget, add up to what each
    # request was served after its first token
    served = {i: 1 for i in range(5)}
    for b in rec.blocks:
        assert b.t0 <= b.t1
        for rid, had, budget, _pos in b.slots:
            assert had == served[rid]
            served[rid] = min(had + b.emitted[rid], budget)
    assert served == dict(enumerate(budgets))
    assert hooks.executable_counts(sched)["prefill"] == 1


def test_cut_window_stops_the_run(engine):
    server = {"max_slots": 2, "prompt_cap": 32, "gen_cap": 24,
              "block_steps": 4}
    sched = hooks.make_scheduler(engine, server)
    reqs = [hooks.make_request(i, np.arange(10, dtype=np.int32), 24, 0.0)
            for i in range(6)]
    rec = hooks.Recorder(sched, cut_s=0.0)
    rec.install()
    outs = rec.run(reqs)
    # the first hook call already finds the window closed
    assert outs == [] and rec.admits == [] and rec.blocks == []


def test_a_long_span_is_recorded_as_a_stall(engine):
    """A decode block that waits STALL_S or more is kept with where the
    main thread was waiting and the CPU counters over it."""
    import time

    server = {"max_slots": 2, "prompt_cap": 32, "gen_cap": 16,
              "block_steps": 4}
    sched = hooks.make_scheduler(engine, server)
    decode0, slowed = sched._decode, []

    def slow_decode(*args):
        if not slowed:
            slowed.append(1)
            time.sleep(hooks.STALL_S + 0.3)
        return decode0(*args)

    sched._decode = slow_decode
    reqs = [hooks.make_request(i, np.arange(10, dtype=np.int32), 6, 0.0)
            for i in range(2)]
    rec = hooks.Recorder(sched)
    rec.install()
    assert [o.status for o in rec.run(reqs)] == ["ok"] * 2
    # (on the CPU the first admission compiles, and may stall too)
    stalls = [s for s in rec.stalls if s.kind == "decode"]
    assert len(stalls) == 1
    stall = stalls[0]
    assert stall.seconds >= hooks.STALL_S + 0.3
    assert any("slow_decode" in f for f in stall.stack)
    assert stall.process_s >= 0
    assert rec.watch_gap[0] >= 0.05
