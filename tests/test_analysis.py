"""The static-analysis gate: every analyzer has a red path and the real
engine is clean.

Two halves, mirroring the CI contract:

* **Red tests** — each rule catches a deliberately broken fixture.  Real
  traced fixtures where JAX lets the violation exist (non-dividing
  BlockSpec, raw int8 cast, f32 collective, trained-threshold qparams);
  stub equations where it does not (pallas refuses tracer capture at
  trace time, jnp inserts explicit converts before mixed-dtype
  arithmetic — the rules still guard hand-lowered graphs, so they are
  unit-tested against synthetic equations).
* **Clean tests** — the serving entry points at smoke shapes, the real
  kernel sources, and the converted engine's qparams/cache produce zero
  findings, so the CI lane's "fail on any finding" gate is meaningful.

Also here: the canned-HLO unit tests for launch/hlo_analysis.py (trip
counts under both while-operand orderings, collective byte accounting,
the unparseable-condition -> 1 fallback) and the repro_lint rule tests
over tmp-file fixtures.
"""
import importlib.util
import json
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budgets as BU
from repro.analysis import donation as DO
from repro.analysis import dtype_drift as DD
from repro.analysis import entrypoints as EP
from repro.analysis import pallas_contracts as PC
from repro.analysis.dtype_drift import AllowRule
from repro.analysis.jaxprs import find_eqns
from repro.analysis.report import (Finding, make_report, validate_report,
                                   write_report)
from repro.launch import hlo_analysis as H

ROOT = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "repro_lint", ROOT / "scripts" / "repro_lint.py")
RL = importlib.util.module_from_spec(_spec)
sys.modules["repro_lint"] = RL
_spec.loader.exec_module(RL)


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# report schema
# ---------------------------------------------------------------------------

class TestReportSchema:
    def test_roundtrip(self, tmp_path):
        f = Finding(analyzer="lint", code="lint.syntax", message="boom")
        rep = make_report([f], tool="repro_lint", entry_points=["src"],
                          backend="cpu")
        assert validate_report(rep) == []
        out = tmp_path / "r.json"
        write_report(str(out), rep)
        assert validate_report(json.loads(out.read_text())) == []

    def test_counts_mismatch_rejected(self):
        rep = make_report([], tool="t")
        bad = dict(rep, counts={"error": 1, "warning": 0})
        assert any("tally" in e for e in validate_report(bad))

    def test_entry_point_count_mismatch_rejected(self):
        rep = make_report([], tool="t", entry_points=["a", "b"])
        bad = dict(rep, n_entry_points=3)
        assert any("n_entry_points" in e for e in validate_report(bad))

    def test_bad_severity_rejected(self):
        rep = json.loads(json.dumps(make_report(
            [Finding(analyzer="a", code="c", message="m")], tool="t")))
        rep["findings"][0]["severity"] = "fatal"
        assert any("severity" in e for e in validate_report(rep))

    def test_write_refuses_invalid(self, tmp_path):
        rep = make_report([], tool="t")
        rep["schema_version"] = 99
        with pytest.raises(ValueError, match="refusing"):
            write_report(str(tmp_path / "x.json"), rep)
        assert not (tmp_path / "x.json").exists()


# ---------------------------------------------------------------------------
# dtype drift
# ---------------------------------------------------------------------------

def _var(dt, shape):
    return NS(aval=NS(dtype=np.dtype(dt), shape=shape))


class TestDtypeDrift:
    def test_promote_red_stub(self):
        # jnp always converts the narrow operand first, so a mixed-dtype
        # add only exists in hand-lowered graphs — unit-test the rule on
        # a synthetic equation
        eqn = NS(primitive=NS(name="add"),
                 invars=[_var("bfloat16", (4,)), _var("float32", (4,))],
                 outvars=[_var("float32", (4,))], params={},
                 source_info=None)
        assert codes(DD.check_dtype_drift(NS(eqns=[eqn]))) == \
            ["drift.promote"]

    def test_explicit_convert_clean(self):
        jx = jax.make_jaxpr(lambda a, b: a.astype(jnp.float32) + b)(
            jnp.ones((4,), jnp.bfloat16), jnp.ones((4,), jnp.float32))
        assert DD.check_dtype_drift(jx) == []

    def test_raw_int_cast_red(self):
        jx = jax.make_jaxpr(lambda x: x.astype(jnp.int8))(
            jnp.ones((4,), jnp.float32))
        assert codes(DD.check_dtype_drift(jx)) == ["drift.raw-int-cast"]

    def test_quantizer_cast_clean(self):
        # round/clip arrive as pjit-wrapped sub-jaxprs: the ancestry walk
        # must see through the wrapper or every real quantizer is flagged
        jx = jax.make_jaxpr(
            lambda x: jnp.clip(jnp.round(x / 0.1), -127, 127)
            .astype(jnp.int8))(jnp.ones((4,), jnp.float32))
        assert DD.check_dtype_drift(jx) == []

    def test_float_collective_red(self):
        jx = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                            axis_env=[("i", 2)])(jnp.ones((4,), jnp.float32))
        assert codes(DD.check_dtype_drift(jx)) == ["drift.collective"]

    def test_int_collective_clean(self):
        jx = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                            axis_env=[("i", 2)])(jnp.ones((4,), jnp.int32))
        assert DD.check_dtype_drift(jx) == []

    def test_allowlist_scalar_pmax_in_scope(self):
        # same shape as dist/collectives.py::compressed_psum — the scoped
        # one-scalar AllowRule admits it
        def compressed_psum(x):
            return jax.lax.pmax(jnp.max(jnp.abs(x)), "i")

        jx = jax.make_jaxpr(compressed_psum, axis_env=[("i", 2)])(
            jnp.ones((8,), jnp.float32))
        assert DD.check_dtype_drift(jx) == []

    def test_allowlist_max_elems_bounds_the_hole(self):
        # the exemption is ONE scalar: a tensor pmax in the same scope
        # must still be flagged
        def compressed_psum(x):
            return jax.lax.pmax(x, "i")

        jx = jax.make_jaxpr(compressed_psum, axis_env=[("i", 2)])(
            jnp.ones((8,), jnp.float32))
        assert codes(DD.check_dtype_drift(jx)) == ["drift.collective"]

    def test_allow_rule_matching(self):
        rule = AllowRule(code="drift.collective", primitive="pmax",
                         max_elems=1, note="n")
        eqn = NS(primitive=NS(name="pmax"), source_info=None)
        assert rule.matches("drift.collective", eqn, 1)
        assert not rule.matches("drift.collective", eqn, 2)
        assert not rule.matches("drift.promote", eqn, 1)
        assert not rule.matches(
            "drift.collective", NS(primitive=NS(name="psum"),
                                   source_info=None), 1)


# ---------------------------------------------------------------------------
# pallas contracts
# ---------------------------------------------------------------------------

def _decode_attention_jaxpr(kv_bits=8):
    from repro.kernels import ops
    b, s, kv, g, d = 2, 32, 3, 4, 16
    dp = d if kv_bits == 8 else d // 2
    q = jnp.ones((b, kv, g, d), jnp.float32)
    kp = jnp.ones((b, s, kv, dp), jnp.int8)
    vp = jnp.ones((b, s, kv, dp), jnp.int8)
    sc = jnp.ones((kv,), jnp.float32)
    return jax.make_jaxpr(
        lambda *a: ops.decode_attention(*a, block_s=16, kv_bits=kv_bits))(
        q, kp, vp, sc, sc, jnp.int32(7))


class TestPallasContracts:
    def test_real_kernel_clean(self):
        jx = _decode_attention_jaxpr()
        assert PC.check_pallas_jaxpr(jx, expect_interpret=True) == []

    def test_int4_kernel_clean(self):
        # dp = D/2 nibbles: the packing rule must see the pool and accept
        jx = _decode_attention_jaxpr(kv_bits=4)
        assert PC.check_pallas_jaxpr(jx, expect_interpret=True) == []

    def test_interpret_mismatch_red(self):
        jx = _decode_attention_jaxpr()
        assert codes(PC.check_pallas_jaxpr(jx, expect_interpret=False)) == \
            ["pallas.interpret"]

    def test_block_divide_red(self):
        import jax.experimental.pallas as pl

        def _copy(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def bad(x):
            # (3,) blocks over an (8,) operand: pallas pads silently
            return pl.pallas_call(
                _copy, grid=(3,),
                in_specs=[pl.BlockSpec((3,), lambda i: (i,))],
                out_specs=pl.BlockSpec((3,), lambda i: (i,)),
                out_shape=jax.ShapeDtypeStruct((8,), x.dtype),
                interpret=True)(x)

        jx = jax.make_jaxpr(bad)(jnp.ones((8,)))
        found = PC.check_pallas_jaxpr(jx, expect_interpret=True)
        assert set(codes(found)) == {"pallas.block-divide"}
        assert len(found) == 2  # input and output operand both flagged

    def test_prefetch_arity_red(self):
        # doctor a REAL launch's grid mapping: claim one more scalar-
        # prefetch operand than the index maps were written for
        eqn = find_eqns(_decode_attention_jaxpr(), "pallas_call")[0]
        gm = eqn.params["grid_mapping"]
        doctored = gm.replace(num_index_operands=gm.num_index_operands + 1)
        found = PC._block_mapping_findings(eqn, doctored, "t")
        assert set(codes(found)) == {"pallas.prefetch-arity"}

    def test_int4_packing_red_stub(self):
        def bm(shape, dt):
            return NS(array_aval=NS(shape=shape, dtype=np.dtype(dt)))

        # pool width 12 against head dim 16: neither D nor D/2
        gm = NS(block_mappings=[bm((2, 16, 3, 16), "float32"),
                                bm((2, 16, 3, 12), "int8")], num_inputs=2)
        assert codes(PC._packing_findings(NS(source_info=None), gm, "t")) \
            == ["pallas.int4-packing"]
        ok = NS(block_mappings=[bm((2, 16, 3, 16), "float32"),
                                bm((2, 16, 3, 8), "int8")], num_inputs=2)
        assert PC._packing_findings(NS(source_info=None), ok, "t") == []

    def test_kernel_closure_red_stub(self):
        # pallas itself rejects tracer capture at trace time on this
        # backend, so the constvar case is pinned with a stub equation
        kern = NS(constvars=[NS(aval="f32[4]")])
        gm = NS(grid=(), num_index_operands=0, num_inputs=0,
                block_mappings=[])
        eqn = NS(primitive=NS(name="pallas_call"),
                 params={"grid_mapping": gm, "interpret": True,
                         "jaxpr": kern},
                 source_info=None)
        assert codes(PC.check_pallas_jaxpr(NS(eqns=[eqn]),
                                           expect_interpret=True)) == \
            ["pallas.kernel-closure"]

    def test_source_rules_red(self):
        bad = textwrap.dedent("""\
            import functools
            from jax.experimental import pallas as pl

            def build(x, table):
                def _kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...]
                bound = functools.partial(_kernel, table=table)
                return pl.pallas_call(bound, grid=(4,),
                                      in_specs=[pl.BlockSpec(
                                          (8,), lambda i: (i + table,))],
                                      out_shape=None)(x)
            """)
        assert codes(PC.check_source_text(bad)) == [
            "pallas.interpret-threading", "pallas.interpret-threading",
            "pallas.static-capture", "pallas.static-capture"]

    def test_source_rules_clean(self):
        good = textwrap.dedent("""\
            import functools
            import jax
            from jax.experimental import pallas as pl

            @functools.partial(jax.jit, static_argnames=("block",))
            def build(x, block, interpret):
                def _kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...]
                bound = functools.partial(_kernel, block=block)
                return pl.pallas_call(bound, grid=(4,),
                                      in_specs=[pl.BlockSpec(
                                          (block,), lambda i: (i,))],
                                      interpret=interpret,
                                      out_shape=None)(x)
            """)
        assert PC.check_source_text(good) == []

    def test_module_registry_red(self, tmp_path):
        (tmp_path / "rogue.py").write_text(
            "from jax.experimental import pallas as pl\n\n"
            "def f(x):\n"
            "    return pl.pallas_call(lambda r, o: None,"
            " out_shape=None)(x)\n")
        assert codes(PC.check_kernel_sources(str(tmp_path))) == \
            ["pallas.module-registry"]

    def test_real_kernel_sources_clean(self):
        assert PC.check_kernel_sources() == []


# ---------------------------------------------------------------------------
# budgets
# ---------------------------------------------------------------------------

class TestBudgets:
    def test_clean_counts(self):
        counts = {"prefill": 1, "decode": 1, "insert": 1, "resume": 0}
        assert BU.check_executable_budgets(counts) == []
        assert BU.check_executable_budgets(counts,
                                           require_all_ran=True) == []

    def test_retrace_red(self):
        found = BU.check_executable_budgets({"decode": 3})
        assert codes(found) == ["budget.retrace"]

    def test_undeclared_piece_red(self):
        found = BU.check_executable_budgets({"decode": 1, "newpiece": 1})
        assert codes(found) == ["budget.undeclared"]

    def test_never_traced_red(self):
        counts = {"prefill": 0}
        assert BU.check_executable_budgets(counts) == []  # partial session
        assert codes(BU.check_executable_budgets(
            counts, require_all_ran=True)) == ["budget.never-traced"]

    def test_compile_watch_cold_then_warm(self):
        @jax.jit
        def f(x):
            return x * 2.0 + 1.0

        x = jnp.arange(7.0)
        with BU.CompileWatch() as cold:
            f(x).block_until_ready()
        assert cold.count >= 1
        assert codes(cold.check(max_compiles=0, what="cold jit")) == \
            ["budget.compile"]
        with BU.CompileWatch() as warm:
            f(x).block_until_ready()
        assert warm.count == 0
        assert warm.check(max_compiles=0, what="warm jit") == []


# ---------------------------------------------------------------------------
# donation / freeze
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def calibrated_qparams():
    from repro.configs import get_config
    from repro.core import api as A
    from repro.launch import steps as ST
    from repro.models import build_model

    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    policy = A.QuantPolicy(kv_int8=True)
    qp = A.init_qparams(model, params, policy)
    qp = ST.make_calibrate_step(model, cfg, policy)(params, qp,
                                                    {"tokens": toks})
    return qp, policy


class TestDonationAndFreeze:
    def test_duplicate_buffer_red(self):
        x = jnp.arange(4.0)
        found = DO.check_duplicate_donation({"a": x, "b": x,
                                             "c": jnp.arange(3.0)})
        assert codes(found) == ["donate.duplicate-buffer"]

    def test_distinct_buffers_clean(self):
        # distinct VALUES on purpose: identical constants could legally
        # share a deduplicated buffer
        tree = {"a": jnp.arange(4.0), "b": jnp.arange(4.0) + 1.0}
        assert DO.check_duplicate_donation(tree) == []

    def test_trained_thresholds_red(self, calibrated_qparams):
        from repro.core import api as A

        qp, policy = calibrated_qparams
        qp_t = A.finalize_calibration(qp, policy, train_thresholds=True)
        assert codes(DO.check_frozen_qparams(qp_t)) == \
            ["freeze.log2_t-leaf", "freeze.trainable-mask"]

    def test_frozen_qparams_clean(self, calibrated_qparams):
        from repro.core import api as A

        qp, policy = calibrated_qparams
        assert DO.check_frozen_qparams(
            A.finalize_calibration(qp, policy)) == []
        # and freeze_thresholds undoes the trained parameterization
        qp_t = A.finalize_calibration(qp, policy, train_thresholds=True)
        assert DO.check_frozen_qparams(A.freeze_thresholds(qp_t)) == []

    def test_fake_quant_eqn_red(self):
        from repro.kernels import ops

        jx = jax.make_jaxpr(
            lambda x: ops.fake_quant(x, jnp.float32(2.0),
                                     jnp.float32(0.9)))(jnp.ones((8, 16)))
        assert codes(DO.check_no_fake_quant(jx)) == ["freeze.fake-quant-eqn"]

    def test_real_kernel_no_fake_quant(self):
        assert DO.check_no_fake_quant(_decode_attention_jaxpr()) == []


# ---------------------------------------------------------------------------
# the serving surface is clean
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def entry_points_int8():
    return EP.build_entry_points("smollm-135m", use_pallas=True, kv_bits=8)


class TestEntryPointsClean:
    def test_all_entries_traced(self, entry_points_int8):
        assert {ep.name for ep in entry_points_int8} == {
            "prefill", "chunked_prefill", "decode_loop", "decode_block",
            "resume", "speculative_verify"}

    def test_zero_findings(self, entry_points_int8):
        found = EP.analyze_entry_points(entry_points_int8)
        assert found == [], "\n".join(
            f"{f.entry_point}: {f.code}: {f.message}" for f in found)

    def test_pallas_actually_on_the_surface(self, entry_points_int8):
        # the clean verdict is vacuous unless the traced graphs really
        # contain pallas launches
        n = sum(len(find_eqns(ep.jaxpr, "pallas_call"))
                for ep in entry_points_int8)
        assert n > 0


# ---------------------------------------------------------------------------
# hlo_analysis (canned post-optimization HLO text)
# ---------------------------------------------------------------------------

_LOOPED_HLO = textwrap.dedent("""\
    HloModule m

    %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %one = s32[] constant(1)
      %next = s32[] add(%i, %one)
      %a = f32[8,4] constant({...})
      %b = f32[4,8] constant({...})
      %d = f32[8,8] dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %r = (s32[], f32[8,8]) tuple(%next, %d)
    }

    %cond (q: (s32[], f32[8,8])) -> pred[] {
      %q = (s32[], f32[8,8]) parameter(0)
      %j = s32[] get-tuple-element(%q), index=0
      %n = s32[] constant(5)
      %lt = pred[] compare(%j, %n), direction=LT
    }

    ENTRY %main (arg: f32[8,8]) -> (s32[], f32[8,8]) {
      %arg = f32[8,8] parameter(0)
      %z = s32[] constant(0)
      %t = (s32[], f32[8,8]) tuple(%z, %arg)
      %w = (s32[], f32[8,8]) while(%t), condition=%cond, body=%body
    }
    """)

# one dot per iteration: 2 * prod(out 8x8) * contracted 4 = 512 flops
_DOT_FLOPS = 2 * 64 * 4


class TestHloAnalysis:
    def test_parse_computations(self):
        comps, entry = H.parse_computations(_LOOPED_HLO)
        assert entry == "main"
        assert set(comps) == {"main", "cond", "body"}

    def test_trip_counted_dot_flops_condition_first(self):
        assert H.analyze(_LOOPED_HLO).dot_flops == 5 * _DOT_FLOPS

    def test_trip_counted_dot_flops_body_first(self):
        # the while operands print in either order depending on the XLA
        # version — both must resolve to the same trip count
        rev = _LOOPED_HLO.replace("condition=%cond, body=%body",
                                  "body=%body, condition=%cond")
        assert H.analyze(rev).dot_flops == 5 * _DOT_FLOPS

    def test_unparseable_condition_counts_once(self):
        # no s32[] constant in the condition: conservatively trip 1
        degenerate = _LOOPED_HLO.replace(
            "  %n = s32[] constant(5)\n", "").replace(
            "compare(%j, %n)", "compare(%j, %j)")
        assert H.analyze(degenerate).dot_flops == _DOT_FLOPS

    def test_collective_bytes(self):
        hlo = textwrap.dedent("""\
            HloModule c

            %sum (x: f32[], y: f32[]) -> f32[] {
              %x = f32[] parameter(0)
              %y = f32[] parameter(1)
              %s = f32[] add(%x, %y)
            }

            ENTRY %main (p: f32[1024]) -> f32[1024] {
              %p = f32[1024] parameter(0)
              %ar = f32[1024] all-reduce(%p), to_apply=%sum
            }
            """)
        costs = H.analyze(hlo)
        assert costs.collective_bytes == 1024 * 4
        assert costs.collective_by_kind["all-reduce"] == 1024 * 4
        assert costs.collective_by_kind["all-gather"] == 0.0


# ---------------------------------------------------------------------------
# repro_lint
# ---------------------------------------------------------------------------

def _lint(tmp_path, src, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return RL.lint_paths([p])


class TestReproLint:
    def test_tracer_cast_red(self, tmp_path):
        found = _lint(tmp_path, """\
            import jax.numpy as jnp

            def schedule(step):
                lr = jnp.cos(step)
                return lr * float(step)
            """)
        assert codes(found) == ["lint.tracer-cast"]

    def test_tracer_cast_suppressed(self, tmp_path):
        found = _lint(tmp_path, """\
            import jax.numpy as jnp

            def schedule(step):
                lr = jnp.cos(step)
                return lr * float(step)  # repro-lint: ok
            """)
        assert found == []

    def test_host_handoff_cast_clean(self, tmp_path):
        # int(rid) fed straight INTO a jax call runs before tracing —
        # an explicit host->device handoff, not a tracer readback
        found = _lint(tmp_path, """\
            import jax

            def slot_key(key, rid):
                return jax.random.fold_in(key, int(rid))
            """)
        assert found == []

    def test_host_in_scan_red(self, tmp_path):
        found = _lint(tmp_path, """\
            import time
            from jax import lax

            def body(c, x):
                t = time.time()
                return c + t, x

            def run(xs):
                return lax.scan(body, 0.0, xs)
            """)
        assert codes(found) == ["lint.host-in-scan"]

    def test_jit_method_red(self, tmp_path):
        found = _lint(tmp_path, """\
            import jax

            class Engine:
                @jax.jit
                def step(self, x):
                    return x
            """)
        assert codes(found) == ["lint.jit-method"]

    def test_undocumented_flag_red(self, tmp_path):
        found = _lint(tmp_path, """\
            import argparse

            ap = argparse.ArgumentParser()
            ap.add_argument("--verbose")
            ap.add_argument("--arch", help="model architecture")
            """)
        assert codes(found) == ["lint.undocumented-flag"]
        assert "--verbose" in found[0].message

    def test_syntax_error_is_a_finding(self, tmp_path):
        found = _lint(tmp_path, "def broken(:\n")
        assert codes(found) == ["lint.syntax"]

    def test_repo_is_lint_clean(self):
        found = RL.lint_paths([ROOT / d for d in
                               ("src", "tests", "scripts", "benchmarks",
                                "examples")])
        assert found == [], "\n".join(
            f"{f.location}: {f.code}" for f in found)
