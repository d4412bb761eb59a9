import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

MUST be run as a script/module — the two lines above execute before any
other import (jax locks the device count on first init).

Per cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract (ShapeDtypeStruct) params/qparams/batch/cache,
  3. assigns shardings from repro.dist.sharding rules,
  4. jit(...).lower(...).compile()  — proving the distribution config is
     coherent (sharding mismatches, compile-time OOM, unsupported
     collectives all fail HERE),
  5. prints memory_analysis() / cost_analysis(),
  6. derives the three roofline terms (compute/memory/collective) with
     v5e constants and writes a JSON report.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, cell_applicable, get_config
from repro.core import api as A
from repro.dist import sharding as SH
from repro.launch import specs as SP
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh

# --- TPU v5e hardware constants (roofline denominators) ---
PEAK_FLOPS_BF16 = 197e12     # per chip
PEAK_FLOPS_INT8 = 394e12
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 4.5e10              # ~50 GB/s usable per link, 1 link per hop

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*?=?\s*\(?([a-z0-9]+)\[([0-9,]*)\]"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f8e4m3": 1,
    "f8e5m2": 1, "s64": 8, "u64": 8, "c64": 8,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective in the
    post-partitioning HLO (shapes in the SPMD module are per-device)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r".*= *(?:\([^)]*\) )?([a-z0-9]+)\[([0-9,]*)\][^ ]* (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)", s)
        if not m:
            # tuple-result collectives: "... = (f32[..], f32[..]) all-reduce"
            m2 = re.match(r".*= *\((.*?)\) (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)", s)
            if not m2:
                continue
            shapes = _SHAPE_RE.findall(m2.group(1))
            kind = m2.group(2)
            nbytes = 0
            for dt, dims in shapes:
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES.get(dt, 4)
        else:
            dt, dims, kind = m.group(1), m.group(2), m.group(3)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes = n * _DTYPE_BYTES.get(dt, 4)
        if "start" in s and f"{kind}-start" in s:
            pass  # async start counted; matching -done carries no payload
        out[kind] += nbytes
        out["count"] += 1
    return out


def build_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               rules: SH.ShardingRules | None = None, verbose: bool = True):
    shape = SHAPES[shape_name]
    cfg = get_config(arch).replace(scan_layers=True)
    from repro.models import build_model

    model = build_model(cfg)
    policy = A.QuantPolicy()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    if rules is None:
        rules = SH.ShardingRules()
    # hillclimb knobs (per-cell overrides without code edits)
    import dataclasses as _dc

    if os.environ.get("REPRO_ACT_SEQ", "") == "none":
        rules = _dc.replace(rules, act_seq=None)
    if os.environ.get("REPRO_KV_LAYOUT"):
        rules = _dc.replace(rules, kv_cache_layout=os.environ["REPRO_KV_LAYOUT"])
    if multi_pod:
        rules = SH.multipod(rules)

    from repro.dist import constraints as CONSTR

    CONSTR.install(rules)

    t0 = time.time()
    if shape.kind == "train":
        params_a, qparams_a = SP.model_state_abstract(model, cfg, policy)
        opt_a = SP.opt_state_abstract(qparams_a)
        batch_a = SP.batch_specs_abstract(cfg, shape)
        p_spec = SH.param_specs(model, params_a, rules)
        q_spec = SH.qparam_specs(model, params_a, qparams_a, rules)
        from repro.optim.adam import AdamState

        o_spec = AdamState(step=SH.P(), mu=q_spec, nu=q_spec)
        b_spec = SH.batch_specs(batch_a, rules)
        # microbatch count: keep one microbatch's activations within HBM;
        # scales with width (activation bytes ~ B*S*d); hillclimb knob
        n_micro = (1 if cfg.d_model < 2048 else
                   4 if cfg.d_model < 5000 else
                   8 if cfg.d_model < 6000 else 16)
        if cfg.ffn == "moe":
            # expert dispatch holds top_k copies of the token stream
            # (hillclimbed: granite-moe top-8 needs the full factor)
            n_micro *= max(2, cfg.top_k)
        if cfg.kind == "hybrid":
            # parallel attn+SSM branches double the per-layer activations
            n_micro *= 4
        if os.environ.get("REPRO_N_MICRO"):
            n_micro = int(os.environ["REPRO_N_MICRO"])
        step_fn = ST.make_fat_train_step(model, cfg, policy, n_micro=n_micro)
        jitted = jax.jit(
            step_fn,
            in_shardings=(
                SH.to_shardings(p_spec, mesh, params_a),
                SH.to_shardings(q_spec, mesh, qparams_a),
                SH.to_shardings(o_spec, mesh, opt_a),
                SH.to_shardings(b_spec, mesh, batch_a),
            ),
            donate_argnums=(1, 2),
        )
        args = (params_a, qparams_a, opt_a, batch_a)
    elif shape.kind == "prefill":
        serve_a, qparams_a = SP.serve_state_abstract(model, cfg, policy)
        batch_a = SP.batch_specs_abstract(cfg, shape)
        batch_a.pop("labels", None)
        cache_len = shape.seq_len if cfg.family != "encdec" else max(
            shape.seq_len // cfg.dec_ratio, 4)
        cache_a = SP.cache_abstract(model, cfg, shape.global_batch, cache_len)
        p_spec = SH.param_specs(model, serve_a, rules)
        q_spec = SH.qparam_specs(model, serve_a, qparams_a, rules)
        b_spec = SH.batch_specs(batch_a, rules)
        c_spec = SH.cache_specs(cache_a, rules, mesh.shape["model"])
        step_fn = ST.make_prefill_step(model, cfg, policy)
        jitted = jax.jit(
            step_fn,
            in_shardings=(
                SH.to_shardings(p_spec, mesh, serve_a),
                SH.to_shardings(q_spec, mesh, qparams_a),
                SH.to_shardings(b_spec, mesh, batch_a),
                SH.to_shardings(c_spec, mesh, cache_a),
            ),
            donate_argnums=(3,),
        )
        args = (serve_a, qparams_a, batch_a, cache_a)
    else:  # decode
        serve_a, qparams_a = SP.serve_state_abstract(model, cfg, policy)
        cache_a = SP.cache_abstract(model, cfg, shape.global_batch,
                                    shape.seq_len)
        tok_a = SP.sds((shape.global_batch, 1), jnp.int32)
        pos_a = SP.sds((), jnp.int32)
        p_spec = SH.param_specs(model, serve_a, rules)
        q_spec = SH.qparam_specs(model, serve_a, qparams_a, rules)
        c_spec = SH.cache_specs(cache_a, rules, mesh.shape["model"])
        t_spec = SH.batch_specs(tok_a, rules)
        step_fn = ST.make_serve_step(model, cfg, policy)
        jitted = jax.jit(
            step_fn,
            in_shardings=(
                SH.to_shardings(p_spec, mesh, serve_a),
                SH.to_shardings(q_spec, mesh, qparams_a),
                SH.to_shardings(t_spec, mesh, tok_a),
                SH.to_shardings(c_spec, mesh, cache_a),
                SH.to_shardings(SH.P(), mesh),
            ),
            donate_argnums=(3,),
        )
        args = (serve_a, qparams_a, tok_a, cache_a, pos_a)

    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    from repro.launch import hlo_analysis as HA

    hlo = HA.analyze(compiled.as_text())

    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    # trip-count-corrected per-device numbers (see hlo_analysis docstring;
    # raw cost_analysis counts every while body once)
    flops = hlo.dot_flops
    bytes_hbm = hlo.approx_bytes
    coll_total = hlo.collective_bytes

    arg_b = getattr(mem, "argument_size_in_bytes", 0) or 0
    temp_b = getattr(mem, "temp_size_in_bytes", 0) or 0
    peak = arg_b + temp_b
    # TPU peak estimate: the CPU backend lowers bf16 dots via f32 upcasts,
    # materializing f32 copies of bf16 weights the MXU never needs
    peak_tpu = peak - hlo.cpu_upcast_artifact_bytes

    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_hbm / HBM_BW
    collective_s = coll_total / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    n = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens  # classic 6ND (fwd+bwd)
        # FAT QAT actual budget: teacher fwd 2ND + student fwd 2ND +
        # student bwd 2ND + remat recompute 2ND ≈ 8ND; noted in §Roofline
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch  # one token per sequence
        model_flops = 2 * n_active * tokens
    useful_ratio = model_flops / max(flops * n_chips, 1.0)

    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": arg_b,
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": temp_b,
            "peak_bytes_per_device": peak,
            "cpu_f32_upcast_artifact_bytes": hlo.cpu_upcast_artifact_bytes,
            "peak_bytes_per_device_tpu_estimate": peak_tpu,
        },
        "cost_analysis_raw": {"flops_per_device": flops_raw,
                              "bytes_per_device": bytes_raw},
        "hlo_corrected": {
            "dot_flops_per_device": flops,
            "approx_bytes_per_device": bytes_hbm,
            "collective_bytes_per_device": coll_total,
            "collective_by_kind": hlo.collective_by_kind,
        },
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "model_flops_global": model_flops,
            "useful_flops_ratio": useful_ratio,
        },
        "params": n,
        "active_params": n_active,
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {report['mesh']}] "
              f"compile {t_compile:.0f}s | peak {peak/1e9:.2f} GB/dev "
              f"(tpu-est {peak_tpu/1e9:.2f}) | flops/dev {flops:.3e} | "
              f"coll {coll_total/1e6:.1f} MB | dominant={dominant}")
        print("  memory_analysis:", report["memory_analysis"])
        print("  cost_analysis(raw): flops=%.3e bytes=%.3e"
              % (flops_raw, bytes_raw))
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture preset to plan (configs.get_config)")
    ap.add_argument("--shape", default=None,
                    help="explicit shape spec name (overrides --arch)")
    ap.add_argument("--all", action="store_true",
                    help="plan every registered architecture")
    ap.add_argument("--multi-pod", action="store_true",
                    help="include the multi-pod mesh variants")
    ap.add_argument("--both-meshes", action="store_true",
                    help="plan both the serving and training meshes")
    ap.add_argument("--out", default="experiments/dryrun",
                    help="directory for the emitted plan JSON files")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                ok, why = cell_applicable(a, s)
                if ok:
                    cells.append((a, s))
                else:
                    print(f"SKIP {a} x {s}: {why}")
    else:
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[cached] {tag}")
                continue
            try:
                rep = build_cell(arch, shape, multi_pod=mp)
                with open(out_path, "w") as f:
                    json.dump(rep, f, indent=1)
            except Exception as e:  # noqa: BLE001 — report all cell failures
                traceback.print_exc()
                failures.append((tag, str(e)[:200]))
    if failures:
        print("\nFAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nDRY-RUN: all requested cells lowered+compiled OK")


if __name__ == "__main__":
    main()
