"""Operations and bytes of ``expert_matmul``: the routed rows of a MoE
layer, grouped by expert, quantized in the kernel and multiplied on the
MXU as int8 by each expert's int8 weights (K, N) with per-(expert,
channel) f32 scales; bf16 rows in and out.  Its rate is the int8 peak.

Three calls a layer (gate and up (d, F), down (F, d)).  A call's
operations are 2 * rows * K * N over the assignments it routes; its
bytes are each expert with a row's weights and scales, read once, and
the rows in and out.  Both are linear in the program's routing counters
on a ``sched.decode`` record (``expert_rows`` and ``experts_live``,
summed over the block's steps and layers), so one block's counts come
from the counters of the record that holds it.  Decode blocks only: an
admission's counters are not recorded.
"""
from bench import spans

OPS_PEAK = "int8_ops"


def cost(rows: int, live: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one call, or of a sum of calls whose rows
    and live experts add up to these."""
    return 2 * rows * k * n, live * (k * n + 4 * n) + rows * (2 * k + 2 * n)


def counters(run, span) -> dict | None:
    """The routing counters of the program's ``sched.decode`` record that
    holds the harness's block ``span``, or None."""
    recs = spans.records(run) or []
    for r in recs:
        if (r.name == "sched.decode" and "expert_rows" in r.attrs
                and r.t0 <= span.t0 and span.t1 <= r.t1):
            return r.attrs
    return None


def calls(run, kind: str, span):
    if kind != "decode":
        return
    attrs = counters(run, span)
    if attrs is None:
        return
    d, f = run.cfg["hidden_size"], run.cfg["moe_intermediate_size"]
    for k, n in ((d, f), (d, f), (f, d)):
        yield cost(attrs["expert_rows"], attrs["experts_live"], k, n)
