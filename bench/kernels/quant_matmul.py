"""Operations and bytes of ``quant_matmul``: bf16 activations (M, K),
quantized in the kernel and multiplied on the MXU as int8 by int8 weights
(K, N) with per-channel f32 scales; bf16 out.  Its rate is the int8 peak.

The calls are a layer's projections as the configuration's architecture
module lists them (``bench/arch/<arch>.py:projections``), at the shape
the kernel is handed: M = the prefill chunk in an admission (every chunk
of the padded prompt runs), M = the slot count in a decode step.
"""
from bench import arch

OPS_PEAK = "int8_ops"


def cost(m: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one call."""
    return 2 * m * k * n, 2 * m * k + k * n + 4 * n + 4 + 2 * m * n


def calls(run, kind: str, span):
    """(operations, bytes) per projection, summed over its calls in one
    admission or one decode block."""
    srv, cfg = run.server, run.cfg
    if kind == "admit":
        m, times = srv["chunk"], srv["prompt_cap"] // srv["chunk"]
    else:
        m, times = srv["max_slots"], srv["block_steps"]
    times *= cfg["num_hidden_layers"]
    for k, n in arch.of(cfg).projections(cfg):
        ops, nbytes = cost(m, k, n)
        yield ops * times, nbytes * times
