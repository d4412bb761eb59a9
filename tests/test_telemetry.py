"""Spans of the serving scheduler (launch/telemetry.py): per-request
timelines and the phases of the host loop, on ``time.monotonic()``.

A tiny scheduler run on the CPU with staggered arrivals, a rejected and a
failed request and one forced preemption; its records must nest, their
leaves must tile the run, and every request must carry ordered host
times.  The jitted pieces must lower under their ``jit_sched_<piece>``
names, which is what the device trace shows.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api as A
from repro.launch import steps as ST
from repro.launch import telemetry as tel
from repro.launch.faults import FaultPlan
from repro.launch.scheduler import Request, SlotScheduler
from repro.models import build_model

LEAF_GAP = 0.02     # share of the run that may fall between leaves


def _parts():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    policy = A.QuantPolicy(kv_int8=True)
    qp = A.init_qparams(model, params, policy)
    qp = ST.make_calibrate_step(model, cfg, policy)(params, qp,
                                                    {"tokens": toks})
    return cfg, model, params, A.finalize_calibration(qp, policy), policy, \
        np.asarray(toks)


@pytest.fixture(scope="module")
def parts():
    return _parts()


def _scheduler(parts, **kw):
    cfg, model, params, qp, policy, _ = parts
    kw = dict(dict(mode="none", max_slots=2, prompt_cap=64, gen_cap=48,
                   prefill_chunk=8, block_steps=16), **kw)
    return SlotScheduler(model, cfg, policy, params, qp, **kw)


@pytest.fixture(scope="module")
def served(parts):
    """(records of the run, completions, scheduler)."""
    toks = parts[-1]
    reqs = [Request(rid=0, tokens=toks[0, :30], max_gen=48),
            Request(rid=1, tokens=toks[1, :12], max_gen=40),
            Request(rid=2, tokens=toks[0, :9], max_gen=32, arrive_ms=15.0),
            Request(rid=3, tokens=toks[1, :0], max_gen=4, arrive_ms=20.0),
            Request(rid=4, tokens=toks[1, :7], max_gen=4, arrive_ms=25.0),
            Request(rid=5, tokens=toks[0, :5], max_gen=3, arrive_ms=60.0)]
    sched = _scheduler(parts, fault_plan=FaultPlan(reject=(4,),
                                                   preempt=((1, 0),)))
    sched.run(reqs)                     # compile: the next run is timed
    done = sched.run(reqs)
    run_id = tel.tracer().run_ids()[-1]
    return tel.tracer().records(run_id), done, sched


def _root(recs):
    (root,) = [r for r in recs if r.name == "sched.run"]
    return root


def _leaves(recs):
    parents = {r.parent_id for r in recs}
    return sorted((r for r in recs if r.span_id not in parents
                   and r.name != "sched.request"), key=lambda r: r.t0)


def test_every_request_has_its_admit_and_request_records(served):
    recs, done, _ = served
    admits = [r.rid for r in recs if r.name == "sched.admit"]
    requests = [r.rid for r in recs if r.name == "sched.request"]
    assert sorted(requests) == sorted(c.rid for c in done) == list(range(6))
    # every request but the rejected one (empty prompt) began an admission,
    # the failed one (injected) included; each exactly once
    assert sorted(admits) == [0, 1, 2, 4, 5]
    assert [r.rid for r in recs if r.name == "sched.readmit"] == [0]
    root = _root(recs)
    assert root.attrs["entry"] == "run" and root.attrs["slots"] == 2
    assert root.t0 == served[2]._rs.t_start


def test_children_lie_inside_their_parents(served):
    recs, _, _ = served
    by_id = {r.span_id: r for r in recs}
    root = _root(recs)
    assert all(r.run_id == root.span_id for r in recs)
    for r in recs:
        assert r.t0 <= r.t1
        if r is root:
            assert r.parent_id is None
            continue
        p = by_id[r.parent_id]
        assert p.t0 <= r.t0 and r.t1 <= p.t1, (r.name, p.name)
    names = {r.name: by_id[r.parent_id].name for r in recs if r is not root}
    assert names["decode.call"] == names["decode.fetch"] == "sched.decode"
    assert names["admit.prefill"] == names["admit.first_token"] == \
        "sched.admit"
    assert names["readmit.resume"] == "sched.readmit"
    assert names["sched.loop"] == names["sched.request"] == "sched.run"


def test_leaves_tile_the_run(served):
    recs, _, _ = served
    root = _root(recs)
    leaves = _leaves(recs)
    for a, b in zip(leaves, leaves[1:]):
        assert a.t1 <= b.t0, (a.name, b.name)
    covered = sum(r.t1 - r.t0 for r in leaves)
    assert covered >= (1 - LEAF_GAP) * (root.t1 - root.t0)
    assert {r.name for r in leaves} >= {
        "sched.loop", "admit.keys", "admit.prefill",
        "admit.insert", "admit.first_token", "readmit.resume",
        "readmit.insert", "decode.transfer", "decode.call", "decode.fetch",
        "decode.collect", "decode.commit"}


def test_request_times_are_ordered(served):
    recs, done, sched = served
    t_start = sched._rs.t_start
    arrive = {0: 0.0, 1: 0.0, 2: 15.0, 3: 20.0, 4: 25.0, 5: 60.0}
    kept = {c.rid: len(c.tokens) for c in done}
    for r in recs:
        if r.name != "sched.request" or r.attrs["status"] != "ok":
            continue
        a = r.attrs
        assert r.t0 == t_start + arrive[r.rid] * 1e-3
        assert r.t0 <= a["t_ingest"] <= a["t_admit"] <= a["t_first"] \
            <= a["t_last"] <= r.t1, r
        assert a["n_tokens"] == kept[r.rid]
    # t_last is the fetch that brought the last kept token
    fetches = {r.t1 for r in recs if r.name == "decode.fetch"}
    assert all(r.attrs["t_last"] in fetches for r in recs
               if r.name == "sched.request" and r.attrs["status"] == "ok")


def test_rejected_and_failed_requests_keep_their_record(served):
    recs, _, _ = served
    req = {r.rid: r for r in recs if r.name == "sched.request"}
    assert req[3].attrs["status"] == "rejected"
    assert req[3].attrs["t_admit"] is None and req[3].attrs["t_ingest"]
    assert req[4].attrs["status"] == "failed"
    assert req[4].attrs["t_admit"] is not None
    assert req[4].attrs["t_first"] is None
    (failed,) = [r for r in recs if r.name == "sched.admit" and r.rid == 4]
    assert failed.attrs["error"] == "InjectedFault"


def test_span_attrs(served):
    recs, _, sched = served
    blocks = [r for r in recs if r.name == "sched.decode"]
    assert [b.attrs["block"] for b in blocks] == list(range(len(blocks)))
    assert all(1 <= b.attrs["active"] <= 2 for b in blocks)
    decoded = sum(b.attrs["kept"] for b in blocks)
    emitted = sum(r.attrs["n_tokens"] - (r.attrs["t_first"] is not None)
                  for r in recs if r.name == "sched.request")
    assert decoded == emitted
    admits = [r for r in recs if r.name == "sched.admit" and r.rid != 4]
    assert all(a.attrs["chunks"] == sched.prompt_cap // sched.prefill_chunk
               and a.attrs["prefix_hit"] is False for a in admits)


def test_decode_blocks_count_live_kv_tiles(parts):
    """``kv_tiles``/``kv_tiles_live`` on ``sched.decode`` against a hand
    count: one request in two slots, the other slot empty, over a
    192-key cache that the dense kernel tiles in two tiles of 96."""
    cfg = parts[0]
    sched = _scheduler(parts, prompt_cap=128, gen_cap=64, prefill_chunk=32,
                       block_steps=8)
    assert sched.cache_len == 192
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 90)
    sched.run([Request(rid=0, tokens=prompt.astype(np.int32), max_gen=20)])
    recs = tel.tracer().records(tel.tracer().run_ids()[-1])
    blocks = [r for r in recs if r.name == "sched.decode"]
    # the admission makes token 1 and leaves the slot at position 90;
    # tokens 2-20 take three blocks of 8 steps, and the kernel runs all
    # 24 over 91-114 keys: 91-96 in one tile, 97 on in two
    assert [b.attrs["kv_tiles_live"] for b in blocks] == [
        6 * 1 + 2 * 2, 8 * 2, 8 * 2]
    # two slots x two tiles x eight steps, the empty slot's included
    assert [b.attrs["kv_tiles"] for b in blocks] == [2 * 2 * 8] * 3


def test_nothing_runnable_is_a_wait(parts):
    sched = _scheduler(parts)
    sched.run([Request(rid=0, tokens=parts[-1][0, :6], max_gen=2,
                       arrive_ms=30.0)])
    recs = tel.tracer().records(tel.tracer().run_ids()[-1])
    leaves = _leaves(recs)
    assert [r.name for r in leaves[:3]] == ["sched.loop", "sched.wait",
                                            "sched.loop"]
    (req,) = [r for r in recs if r.name == "sched.request"]
    assert leaves[1].t1 >= req.t0 and req.attrs["t_ingest"] >= req.t0


def test_buffer_keeps_the_newest_whole_runs():
    tr = tel.Tracer(max_runs=3, max_records=50)
    for run in range(10):
        with tr.span("run", n=run):
            for _ in range(4):
                with tr.span("leaf"):
                    pass
    kept = tr.run_ids()
    assert len(kept) == 3
    assert [tr.records(i)[-1].attrs["n"] for i in kept] == [7, 8, 9]
    with tr.span("long"):
        for _ in range(200):
            with tr.span("leaf"):
                pass
    assert len(tr.records()) <= 50 and len(tr.run_ids()) == 1


def test_process_tracer_stays_bounded(parts):
    sched = _scheduler(parts)
    toks = parts[-1]
    for i in range(tel.MAX_RUNS + 3):
        sched.run([Request(rid=i, tokens=toks[0, :6], max_gen=2)])
    assert len(tel.tracer().run_ids()) <= tel.MAX_RUNS
    assert len(tel.tracer().records()) <= tel.MAX_RECORDS


def test_paused_span_and_jsonl(tmp_path):
    tr = tel.Tracer()
    with tr.span("root") as root:
        with tr.span("loop") as loop:
            with loop.paused():
                with tr.span("work", rid=7):
                    pass
        tr.record("after", 1.0, 2.0, rid=7, status="ok")
    recs = tr.records()
    assert [r.name for r in recs] == ["loop", "work", "loop", "after",
                                      "root"]
    assert all(r.parent_id == root.span_id for r in recs[:-1])
    assert len({r.span_id for r in recs}) == len(recs)
    path = tmp_path / "spans.jsonl"
    assert tr.write_jsonl(path) == len(recs)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert rows[0]["name"] == "after" and rows[0]["attrs"] == {
        "status": "ok"}
    assert {r["name"] for r in rows} == {r.name for r in recs}


def _shapes(args):
    return jax.tree.map(
        lambda x: (jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x))
                   if hasattr(x, "shape") else x), args)


def test_jitted_pieces_lower_under_their_names(parts):
    """Every piece the scheduler dispatches lowers as jit_sched_<piece>:
    the module name the device trace shows."""
    toks = parts[-1]
    seen = {}

    def capture(sched):
        for piece in sched.executable_counts():
            attr = f"_{piece}_fn"
            fn = getattr(sched, attr)

            def wrapped(*args, fn=fn, piece=piece):
                seen.setdefault(piece, (fn, _shapes(args)))
                return fn(*args)
            setattr(sched, attr, wrapped)
        return sched

    dense = capture(_scheduler(parts, fault_plan=FaultPlan(
        preempt=((1, 0),))))
    dense.run([Request(rid=0, tokens=toks[0, :10], max_gen=40)])
    paged = capture(_scheduler(parts, cache_layout="paged", page_size=8))
    paged.run([Request(rid=r, tokens=toks[0, :12], max_gen=3)
               for r in range(2)])
    assert set(seen) == {"prefill", "decode", "insert", "resume", "set_row",
                         "copy_page"}
    for piece, (fn, args) in seen.items():
        text = fn.lower(*args).as_text()
        assert f"module @jit_sched_{piece} " in text, piece
