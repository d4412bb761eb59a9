"""Pallas kernel contract checker.

Two passes over the kernel layer, both static:

**Jaxpr pass** (``check_pallas_jaxpr``) — walks every ``pallas_call``
equation in a traced serving entry point and validates the launch
contract the kernels document in their module docstrings:

``pallas.block-divide``
    Every BlockSpec block shape must divide its operand shape exactly.
    The kernels pick grid-friendly shapes upstream (128-tiled cache
    lengths, chunk multiples) precisely so no pallas-inserted padding
    lands on the hot path — a non-dividing block means a silent copy or
    masked garbage per grid step.

``pallas.prefetch-arity``
    Each index map must take ``len(grid) + num_scalar_prefetch``
    arguments.  The scalar-prefetch operands (block tables, q_start,
    kv_len) ride in SMEM and are appended to every index map's
    signature; an arity mismatch means a table is being ignored or
    misread.

``pallas.int4-packing``
    The ``dp = D/2`` invariant: an int8-dtype KV pool operand must be
    exactly as wide as the float query head dim (kv_bits=8) or exactly
    half (packed int4 nibbles) — any other ratio means the packing and
    the BlockSpec disagree about where bytes live.

``pallas.interpret``
    Every ``pallas_call`` must carry the interpret flag the entry point
    expects (True on this CPU container, False on TPU) — a kernel that
    hardcodes it works in exactly one environment.

``pallas.kernel-closure``
    The kernel jaxpr must be closed (no constvars): a constvar means the
    kernel body captured a traced array from the enclosing scope instead
    of taking it as a Ref — it would be baked into the executable as a
    constant, pinning stale data across calls.

**Source pass** (``check_kernel_sources``) — AST over
``repro.kernels.PALLAS_MODULES``:

``pallas.interpret-threading``
    Every function containing a ``pallas_call`` must expose an
    ``interpret`` parameter and pass ``interpret=`` through — the knob
    must thread end-to-end from ops.py to the launch.

``pallas.static-capture``
    Kernel-body partial bindings and index maps may reference the
    builder's *static* parameters (static_argnames) and shape-derived
    locals, never its array parameters — a direct array-parameter
    reference is tracer capture in the making.

``pallas.module-registry``
    Every module under ``src/repro/kernels`` that calls ``pallas_call``
    must be listed in ``PALLAS_MODULES`` (coverage guard: a kernel
    cannot opt out of contract checking by not being enumerated).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Optional, Sequence

from jax.experimental import pallas as pl

from repro.analysis.jaxprs import eqn_location, iter_eqns
from repro.analysis.report import Finding

_INT_POOL_DTYPES = ("int8", "uint8")


# ---------------------------------------------------------------------------
# jaxpr pass
# ---------------------------------------------------------------------------

def _block_mapping_findings(eqn, gm, entry_point: str) -> list[Finding]:
    out: list[Finding] = []
    loc = eqn_location(eqn)
    want_arity = len(gm.grid) + gm.num_index_operands
    for bi, bm in enumerate(gm.block_mappings):
        arr = bm.array_aval
        block = bm.block_shape
        for dim, (b, a) in enumerate(zip(block, arr.shape)):
            if isinstance(b, pl.Blocked):
                b = b.block_size
            if not isinstance(b, int):
                continue  # squeezed / element-indexed dims
            if b <= 0 or a % b:
                out.append(Finding(
                    analyzer="pallas_contracts", code="pallas.block-divide",
                    entry_point=entry_point, location=loc,
                    message=f"pallas_call operand {bi}: block shape "
                            f"{tuple(block)} does not divide operand shape "
                            f"{tuple(arr.shape)} at dim {dim} ({b} does not "
                            f"divide {a}) — pallas would pad/mask this "
                            "operand per grid step"))
                break
        arity = len(bm.index_map_jaxpr.jaxpr.invars)
        if arity != want_arity:
            out.append(Finding(
                analyzer="pallas_contracts", code="pallas.prefetch-arity",
                entry_point=entry_point, location=loc,
                message=f"pallas_call operand {bi}: index map takes {arity} "
                        f"args but the launch has {len(gm.grid)} grid axes "
                        f"+ {gm.num_index_operands} scalar-prefetch "
                        f"operands = {want_arity} — a prefetch table is "
                        "being dropped or misindexed"))
    return out


def _packing_findings(eqn, gm, entry_point: str) -> list[Finding]:
    """dp = D / (8 / kv_bits): int pools must be exactly D or D/2 wide."""
    in_maps = gm.block_mappings[:gm.num_inputs]
    q_widths = [bm.array_aval.shape[-1] for bm in in_maps
                if len(bm.array_aval.shape) == 4
                and str(bm.array_aval.dtype) not in _INT_POOL_DTYPES
                and "float" in str(bm.array_aval.dtype)
                or len(bm.array_aval.shape) == 4
                and str(bm.array_aval.dtype) == "bfloat16"]
    pools = [bm for bm in in_maps
             if len(bm.array_aval.shape) == 4
             and str(bm.array_aval.dtype) in _INT_POOL_DTYPES]
    if not q_widths or not pools:
        return []
    d = max(q_widths)  # the query head dim (logical width)
    out = []
    for bm in pools:
        dp = bm.array_aval.shape[-1]
        if dp not in (d, d // 2 if d % 2 == 0 else -1):
            out.append(Finding(
                analyzer="pallas_contracts", code="pallas.int4-packing",
                entry_point=entry_point, location=eqn_location(eqn),
                message=f"KV pool operand is {dp} bytes wide against a "
                        f"query head dim of {d}: storage width must be D "
                        f"(int8) or D/2 (packed int4 nibbles) — "
                        "pack/BlockSpec disagreement"))
    return out


def check_pallas_jaxpr(jaxpr, *, entry_point: str = "",
                       expect_interpret: Optional[bool] = None,
                       ) -> list[Finding]:
    """Validate every pallas_call equation reachable from ``jaxpr``."""
    findings: list[Finding] = []
    n_calls = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        n_calls += 1
        gm = eqn.params["grid_mapping"]
        findings += _block_mapping_findings(eqn, gm, entry_point)
        findings += _packing_findings(eqn, gm, entry_point)
        if (expect_interpret is not None
                and bool(eqn.params.get("interpret")) != expect_interpret):
            findings.append(Finding(
                analyzer="pallas_contracts", code="pallas.interpret",
                entry_point=entry_point, location=eqn_location(eqn),
                message=f"pallas_call has interpret="
                        f"{bool(eqn.params.get('interpret'))} but this "
                        f"entry point expects {expect_interpret} (backend-"
                        "selected via kernels.ops) — the flag is not "
                        "threaded end-to-end"))
        kernel_jaxpr = eqn.params.get("jaxpr")
        consts = getattr(kernel_jaxpr, "constvars", ())
        if consts:
            findings.append(Finding(
                analyzer="pallas_contracts", code="pallas.kernel-closure",
                entry_point=entry_point, location=eqn_location(eqn),
                message=f"kernel jaxpr captured {len(consts)} constant(s) "
                        "from the enclosing scope "
                        f"({[str(getattr(c, 'aval', c)) for c in consts]}): "
                        "kernel bodies read arrays through Refs only — a "
                        "captured tracer bakes stale data into the "
                        "executable"))
    return findings


# ---------------------------------------------------------------------------
# source pass
# ---------------------------------------------------------------------------

def _static_argnames(fn: ast.FunctionDef) -> Optional[set]:
    """static_argnames from a @functools.partial(jax.jit, ...) decorator;
    None when the function is not jit-decorated that way."""
    for dec in fn.decorator_list:
        if not (isinstance(dec, ast.Call) and isinstance(dec.func,
                                                         ast.Attribute)
                and dec.func.attr == "partial"):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames" and isinstance(
                    kw.value, (ast.Tuple, ast.List)):
                return {c.value for c in kw.value.elts
                        if isinstance(c, ast.Constant)}
    return None


def _param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _contains_pallas_call(node) -> list[ast.Call]:
    calls = []
    for sub in ast.walk(node):
        if (isinstance(sub, ast.Call)
                and ((isinstance(sub.func, ast.Attribute)
                      and sub.func.attr == "pallas_call")
                     or (isinstance(sub.func, ast.Name)
                         and sub.func.id == "pallas_call"))):
            calls.append(sub)
    return calls


def _loaded_names(node) -> set:
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _check_builder(fn: ast.FunctionDef, path: str) -> list[Finding]:
    findings: list[Finding] = []
    calls = _contains_pallas_call(fn)
    if not calls:
        return findings
    loc = f"{path}:{fn.lineno}"
    params = _param_names(fn)
    if "interpret" not in params:
        findings.append(Finding(
            analyzer="pallas_contracts", code="pallas.interpret-threading",
            location=loc,
            message=f"{fn.name}: contains pallas_call but takes no "
                    "'interpret' parameter — the backend-selection knob "
                    "must thread end-to-end (kernels/ops.py picks it)"))
    for call in calls:
        if not any(kw.arg == "interpret" for kw in call.keywords):
            findings.append(Finding(
                analyzer="pallas_contracts",
                code="pallas.interpret-threading",
                location=f"{path}:{call.lineno}",
                message=f"{fn.name}: pallas_call without an explicit "
                        "interpret= keyword — it would silently default "
                        "to compiled mode on every backend"))
    # static-capture: partial(_kernel, ...) bindings and index-map
    # lambdas/defs may reference static params and locals, never the
    # builder's array parameters
    statics = _static_argnames(fn) or set()
    array_params = [p for p in params
                    if p not in statics and p != "interpret"]
    suspects: list[tuple[str, int, set]] = []
    for sub in ast.walk(fn):
        if (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "partial" and sub.args
                and isinstance(sub.args[0], ast.Name)
                and sub.args[0].id.startswith("_")):  # partial(_kernel, ...)
            used = set()
            for kw in sub.keywords:
                used |= _loaded_names(kw.value)
            suspects.append(("kernel partial binding", sub.lineno, used))
        if isinstance(sub, ast.Lambda):
            suspects.append(("index map", sub.lineno,
                             _loaded_names(sub.body)
                             - {a.arg for a in sub.args.args}))
    for what, lineno, used in suspects:
        captured = sorted(used & set(array_params))
        if captured:
            findings.append(Finding(
                analyzer="pallas_contracts", code="pallas.static-capture",
                location=f"{path}:{lineno}",
                message=f"{fn.name}: {what} references array "
                        f"parameter(s) {captured} — only static args "
                        "(static_argnames) and shape-derived locals may "
                        "be closed over; array data reaches the kernel "
                        "through Refs"))
    return findings


def check_kernel_sources(kernels_dir: Optional[str] = None) -> list[Finding]:
    """AST contract pass over ``repro.kernels.PALLAS_MODULES`` (plus the
    registry coverage guard)."""
    import repro.kernels as K

    if kernels_dir is None:
        kernels_dir = Path(K.__file__).parent
    kernels_dir = Path(kernels_dir)
    findings: list[Finding] = []
    listed = set(K.PALLAS_MODULES)
    for py in sorted(kernels_dir.glob("*.py")):
        tree = ast.parse(py.read_text())
        rel = f"kernels/{py.name}"
        has_calls = bool(_contains_pallas_call(tree))
        if has_calls and py.stem not in listed:
            findings.append(Finding(
                analyzer="pallas_contracts", code="pallas.module-registry",
                location=rel,
                message=f"{py.stem} contains pallas_call but is not in "
                        "repro.kernels.PALLAS_MODULES — register it so "
                        "the contract checker covers it"))
        if py.stem not in listed:
            continue
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                findings += _check_builder(node, rel)
    return findings


def check_source_text(src: str, path: str = "<fixture>") -> list[Finding]:
    """Run the source pass over one module's text (test fixtures)."""
    tree = ast.parse(src)
    findings = []
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            findings += _check_builder(node, path)
    return findings
