"""Pallas kernel layer: the fused int8/int4 serving hot paths.

``ops.py`` holds the jit'd public wrappers (interpret-mode selection per
backend), ``ref.py`` the pure-jnp oracles every kernel is pinned
against.  ``PALLAS_MODULES`` enumerates the modules that contain
``pl.pallas_call`` sites — the static contract checker
(repro.analysis.pallas_contracts) walks exactly this list, so a new
kernel module is added HERE to come under contract, and a module that
stops appearing here fails the checker's coverage guard rather than
silently dropping out of CI.
"""

# module basenames under repro.kernels with pallas_call sites (checked
# by repro.analysis.pallas_contracts.check_kernel_sources)
PALLAS_MODULES = (
    "decode_attention",
    "prefill_attention",
    "quant_matmul",
    "fake_quant",
    "expert_matmul",
)
