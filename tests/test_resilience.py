"""Serving resilience layer: per-request fault isolation, deadline and
preemption scheduling, graceful pool-exhaustion degradation, and the
deterministic fault-injection harness (launch/faults.py).

The chaos acceptance (ISSUE 6): one combined fault plan — bad request +
NaN logits + forced pool exhaustion + forced preemption — in ONE run:
``run()`` completes, every request gets a terminal status, non-faulted
requests are token-identical to the fault-free run, a preempted-then-
re-admitted request matches its uninterrupted output token for token
(greedy), and the executable counts stay pinned across fault plans (the
no-retrace contract: fault schedules are data, never shape).
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api as A
from repro.launch import steps as ST
from repro.launch.faults import FaultPlan
from repro.launch.scheduler import Request, SlotScheduler
from repro.models import build_model

B, S, GEN = 2, 32, 6
CHUNK = 8


@pytest.fixture(scope="module")
def stack():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    policy = A.QuantPolicy(kv_int8=True)
    qp = A.init_qparams(model, params, policy)
    qp = ST.make_calibrate_step(model, cfg, policy)(params, qp,
                                                    {"tokens": toks})
    qp = A.finalize_calibration(qp, policy)
    return cfg, model, params, qp, policy, toks


def _scheduler(model, cfg, policy, params, qp, **kw):
    kw.setdefault("mode", "none")
    kw.setdefault("max_slots", 2)
    kw.setdefault("prompt_cap", S)
    kw.setdefault("gen_cap", GEN + 2)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("block_steps", 3)
    return SlotScheduler(model, cfg, policy, params, qp, **kw)


class TestFaultPlan:
    def test_parse_forms_agree(self, tmp_path):
        want = FaultPlan(reject=(2,), nan_decode=((3, 1),),
                         preempt=((1, 0),), exhaust_prefix=True,
                         ms_per_block=10.0)
        spec = {"reject": [2], "nan_decode": [[3, 1]], "preempt": [[1, 0]],
                "exhaust_prefix": True, "ms_per_block": 10.0}
        assert FaultPlan.parse(spec) == want
        assert FaultPlan.parse(json.dumps(spec)) == want
        p = tmp_path / "plan.json"
        p.write_text(json.dumps(spec))
        assert FaultPlan.parse(str(p)) == want
        # JSON-object pair form: {"rid": step} / {"block": rid}
        assert FaultPlan.parse({"nan_decode": {"3": 1}}).nan_decode \
            == ((3, 1),)
        # passthrough
        assert FaultPlan.parse(want) is want

    def test_parse_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown fault plan keys"):
            FaultPlan.parse({"nan_deocde": [[3, 1]]})
        with pytest.raises(ValueError, match="ms_per_block"):
            FaultPlan(ms_per_block=-1.0)

    def test_hashable_and_queries(self):
        plan = FaultPlan(reject=[5, 2], nan_decode=[(1, 4)],
                         preempt=[(2, 0), (2, 3)])
        assert {plan: 1}[FaultPlan(reject=(2, 5), nan_decode=((1, 4),),
                                   preempt=((2, 0), (2, 3)))] == 1
        assert plan.rejects(2) and not plan.rejects(3)
        assert plan.nan_decode_step(1) == 4
        assert plan.nan_decode_step(9) is None
        assert sorted(plan.preempts_at(2)) == [0, 3]
        assert plan.preempts_at(1) == ()
        assert not plan.empty and FaultPlan().empty
        assert "reject" in plan.describe()
        assert FaultPlan().describe() == "no faults"


class TestIsolation:
    def test_faults_stay_per_request(self, stack):
        """One scheduler, one run, three different per-request faults:
        each faulted request retires with its own terminal status while
        the healthy co-resident finishes normally."""
        cfg, model, params, qp, policy, toks = stack
        plan = FaultPlan(reject=(10,), nan_prefill=(11,),
                         nan_decode=((12, 1),))
        sched = _scheduler(model, cfg, policy, params, qp, fault_plan=plan)
        reqs = [
            Request(rid=10, tokens=np.asarray(toks[0, :9]), max_gen=GEN),
            Request(rid=11, tokens=np.asarray(toks[1, :9]), max_gen=GEN),
            Request(rid=12, tokens=np.asarray(toks[0, :20]), max_gen=GEN),
            Request(rid=13, tokens=np.asarray(toks[1, :20]), max_gen=GEN),
        ]
        done = {c.rid: c for c in sched.run(reqs)}
        assert sorted(done) == [10, 11, 12, 13]
        assert done[10].status == "failed"
        assert "injected admission failure" in done[10].reason
        assert done[10].tokens == []
        assert done[11].status == "failed"
        assert "non-finite prefill logits" in done[11].reason
        # NaN at decode step 1: t0 and step 0 were emitted, then the slot
        # froze — partial output is returned, not discarded
        assert done[12].status == "failed"
        assert "non-finite logits during decode" in done[12].reason
        assert len(done[12].tokens) == 2
        assert done[13].status == "ok" and len(done[13].tokens) == GEN
        h = sched.health_stats()
        assert h["failed"] == 3 and h["ok"] == 1

    def test_server_fault_during_admission_propagates(self, stack,
                                                      monkeypatch):
        """Only the request's own faults retire it as 'failed': a prefill
        that cannot lower or compile is the server's fault and must not
        pass for a served run with every status 'failed'."""
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp)

        def broken(*args):
            raise RuntimeError("Mosaic failed to compile the kernel")

        monkeypatch.setattr(sched, "_prefill", broken)
        with pytest.raises(RuntimeError, match="Mosaic"):
            sched.run([Request(rid=0, tokens=np.asarray(toks[0, :9]),
                               max_gen=GEN)])

    def test_malformed_requests_rejected_not_raised(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp)
        reqs = [
            Request(rid=0, tokens=np.zeros((0,), np.int32), max_gen=GEN),
            Request(rid=1, tokens=np.zeros((S + 1,), np.int32),
                    max_gen=GEN),
            Request(rid=2, tokens=np.asarray(toks[0, :9]), max_gen=0),
            Request(rid=3, tokens=np.asarray(toks[0, :9]), max_gen=GEN),
        ]
        done = {c.rid: c for c in sched.run(reqs)}
        assert done[0].status == "rejected"
        assert "empty prompt" in done[0].reason
        assert done[1].status == "rejected"
        assert "exceeds prompt_cap" in done[1].reason
        assert done[2].status == "rejected"
        assert "max_gen" in done[2].reason
        assert done[3].status == "ok"


class TestChaosAcceptance:
    def test_combined_fault_plan_one_run(self, stack):
        """The ISSUE's chaos suite: clean run, then the SAME scheduler
        under bad-request + NaN-decode + pool-exhaustion + forced-
        preemption in one run."""
        cfg, model, params, qp, policy, toks = stack

        def mk():
            return [
                Request(rid=0, tokens=np.asarray(toks[0, :S]), max_gen=GEN),
                Request(rid=1, tokens=np.asarray(toks[1, :20]),
                        max_gen=GEN),
                Request(rid=2, tokens=np.asarray(toks[0, :9]), max_gen=GEN),
                Request(rid=3, tokens=np.asarray(toks[1, :16]),
                        max_gen=GEN),
            ]

        sched = _scheduler(model, cfg, policy, params, qp,
                           cache_layout="paged", page_size=8)
        clean = {c.rid: c for c in sched.run(mk())}
        assert all(c.status == "ok" for c in clean.values())

        # same scheduler instance => same compiled executables; the plan
        # swap proves fault schedules are data, never shape
        sched._plan = FaultPlan(nan_decode=((1, 1),), preempt=((1, 0),),
                                exhaust_prefix=True)
        reqs = mk() + [Request(rid=4, tokens=np.zeros((0,), np.int32),
                               max_gen=GEN)]
        chaos = {c.rid: c for c in sched.run(reqs)}
        sched._plan = FaultPlan()

        # run() completed and every request carries a terminal status
        assert sorted(chaos) == [0, 1, 2, 3, 4]
        assert chaos[4].status == "rejected"
        assert chaos[1].status == "failed"
        assert "non-finite" in chaos[1].reason
        for rid in (0, 2, 3):
            assert chaos[rid].status == "ok", chaos[rid]
        # preempted-then-re-admitted == uninterrupted, token for token
        assert chaos[0].tokens == clean[0].tokens
        # non-faulted co-residents identical to the fault-free run
        assert chaos[2].tokens == clean[2].tokens
        assert chaos[3].tokens == clean[3].tokens

        h = sched.health_stats()
        assert h["preemptions"] >= 1 and h["readmits"] >= 1
        assert h["prefix_exhausted"] >= 1
        # no-retrace across fault plans (resume traced by the preemption)
        counts = sched.executable_counts()
        assert counts == {"prefill": 1, "decode": 1, "insert": 1,
                          "resume": 1, "set_row": 1, "copy_page": 1}, counts


class TestDeadlines:
    def test_resident_deadline_times_out_at_boundary(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp, gen_cap=40,
                           fault_plan=FaultPlan(ms_per_block=10.0))
        (c,) = sched.run([Request(rid=0, tokens=np.asarray(toks[0, :9]),
                                  max_gen=30, deadline_ms=25.0)])
        assert c.status == "timeout"
        assert "while decoding" in c.reason
        # virtual clock: 10 ms/block, reaped at the first boundary past
        # 25 ms => exactly 3 blocks of partial output survive
        assert len(c.tokens) == 1 + 3 * 3
        assert sched.health_stats()["deadline_misses"] == 1

    def test_queued_deadline_times_out_without_device_work(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp, max_slots=1,
                           fault_plan=FaultPlan(ms_per_block=10.0))
        reqs = [Request(rid=0, tokens=np.asarray(toks[0, :9]), max_gen=GEN),
                Request(rid=1, tokens=np.asarray(toks[1, :9]), max_gen=GEN,
                        deadline_ms=5.0)]
        done = {c.rid: c for c in sched.run(reqs)}
        assert done[0].status == "ok"
        assert done[1].status == "timeout"
        assert "while queued" in done[1].reason
        assert done[1].tokens == []


class TestPriorityPreemption:
    def test_high_priority_waiter_evicts_lowest_priority_slot(self, stack):
        """A full engine + a strictly-higher-priority arrival: the lowest
        priority resident parks, the VIP runs, the victim re-admits and
        still produces its full uninterrupted output."""
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp, gen_cap=20,
                           fault_plan=FaultPlan(ms_per_block=10.0))
        ref = _scheduler(model, cfg, policy, params, qp, gen_cap=20)
        want = {c.rid: c.tokens for c in ref.run(
            [Request(rid=0, tokens=np.asarray(toks[0, :9]), max_gen=12)])}
        reqs = [
            Request(rid=0, tokens=np.asarray(toks[0, :9]), max_gen=12,
                    priority=0),
            Request(rid=1, tokens=np.asarray(toks[1, :9]), max_gen=12,
                    priority=0),
            Request(rid=2, tokens=np.asarray(toks[0, :20]), max_gen=GEN,
                    priority=5, arrive_ms=10.0),
        ]
        done = {c.rid: c for c in sched.run(reqs)}
        assert all(c.status == "ok" for c in done.values())
        h = sched.health_stats()
        assert h["preemptions"] == 1 and h["readmits"] == 1
        assert sched.call_counts()["resume"] == 1
        # victim slot 0 (lowest priority, lowest slot) round-tripped
        # through park/re-admit with token-identical output
        assert done[0].tokens == want[0]
        assert len(done[2].tokens) == GEN

    def test_equal_priorities_never_preempt(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp,
                           fault_plan=FaultPlan(ms_per_block=10.0))
        reqs = [Request(rid=r, tokens=np.asarray(toks[r % B, :9]),
                        max_gen=GEN, arrive_ms=float(5 * r))
                for r in range(4)]
        done = sched.run(reqs)
        assert all(c.status == "ok" for c in done)
        assert sched.health_stats()["preemptions"] == 0


class TestDegradation:
    def test_bounded_queue_sheds_under_overload(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp, max_slots=1,
                           queue_cap=1)
        reqs = [Request(rid=r, tokens=np.asarray(toks[r % B, :9]),
                        max_gen=2) for r in range(3)]
        done = {c.rid: c for c in sched.run(reqs)}
        assert done[0].status == "ok"
        assert done[1].status == "shed" and done[2].status == "shed"
        assert "queue_cap=1" in done[1].reason
        assert sched.health_stats()["shed"] == 2

    def test_block_policy_holds_arrivals_instead(self, stack):
        cfg, model, params, qp, policy, toks = stack
        sched = _scheduler(model, cfg, policy, params, qp, max_slots=1,
                           queue_cap=1, shed_policy="block")
        reqs = [Request(rid=r, tokens=np.asarray(toks[r % B, :9]),
                        max_gen=2) for r in range(3)]
        done = sched.run(reqs)
        assert sorted(c.rid for c in done) == [0, 1, 2]
        assert all(c.status == "ok" for c in done)
        assert sched.health_stats()["shed"] == 0

    def test_invalid_knobs_reject_at_construction(self, stack):
        cfg, model, params, qp, policy, toks = stack
        with pytest.raises(ValueError, match="queue_cap"):
            _scheduler(model, cfg, policy, params, qp, queue_cap=0)
        with pytest.raises(ValueError, match="shed_policy"):
            _scheduler(model, cfg, policy, params, qp, shed_policy="drop")


class TestSamplingDeterminism:
    def test_same_seed_different_arrival_order(self, stack):
        """Satellite: per-request PRNG keys (fold_in(seed, rid)) make
        sampled outputs a function of the request, not of arrival order
        or slot placement — reversing the queue and changing the slot
        count both leave every request's tokens bit-identical."""
        cfg, model, params, qp, policy, toks = stack
        kw = dict(temperature=0.8, seed=7)

        def mk():
            return [Request(rid=r, tokens=np.asarray(toks[r % B, :n]),
                            max_gen=GEN)
                    for r, n in enumerate([32, 20, 9])]

        a = {c.rid: c.tokens for c in _scheduler(
            model, cfg, policy, params, qp, **kw).run(mk())}
        b = {c.rid: c.tokens for c in _scheduler(
            model, cfg, policy, params, qp, **kw).run(
                list(reversed(mk())))}
        c3 = {c.rid: c.tokens for c in _scheduler(
            model, cfg, policy, params, qp, max_slots=3, **kw).run(mk())}
        assert a == b
        assert a == c3
        # sanity: sampling actually happened (streams differ per request)
        assert len(set(map(tuple, a.values()))) > 1


class TestEngineReport:
    def test_engine_aggregates_outcomes_and_parses_plans(self, stack):
        from repro.launch.engine import Engine

        cfg, model, params, qp, policy, toks = stack
        engine = Engine(model, cfg, policy, params, qp, mode="none",
                        fault_plan={"reject": [0]})
        assert engine.health_report() == {}   # no scheduler yet
        reqs = [Request(rid=0, tokens=np.asarray(toks[0, :9]), max_gen=2),
                Request(rid=1, tokens=np.asarray(toks[1, :9]), max_gen=2)]
        done = {c.rid: c for c in engine.generate(
            reqs, max_slots=2, prompt_cap=S, gen_cap=GEN, block_steps=3)}
        assert done[0].status == "failed"
        assert done[1].status == "ok"
        h = engine.health_report()
        assert h["failed"] == 1 and h["ok"] == 1
        with pytest.raises(ValueError, match="shed_policy"):
            Engine(model, cfg, policy, params, qp, mode="none",
                   shed_policy="drop")
