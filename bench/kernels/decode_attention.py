"""Operations and bytes of ``decode_attention``: one new query per slot
against the int8 K/V of every position up to its own.

Counted per decoding slot and step: 4 * head_dim operations per query
head and key, each key and value read once as int8, f32 query in and
out.  A slot decodes every step of the block it entered active.  Its
rate is the bf16 peak.
"""
OPS_PEAK = "bf16_flops"


def cost(n_keys: int, heads: int, kv_heads: int, head_dim: int) -> tuple:
    """(operations, bytes) of one slot's query over ``n_keys`` keys."""
    return (4 * n_keys * heads * head_dim,
            2 * n_keys * kv_heads * head_dim + 2 * 4 * heads * head_dim)


def calls(run, kind: str, span):
    if kind != "decode":
        return
    cfg, steps = run.cfg, run.server["block_steps"]
    n = cfg["num_hidden_layers"]
    for j in range(steps):
        # one call per step over every slot; a slot's pending token sits
        # at ``pos`` and attends keys 0..pos
        ops = nbytes = 0
        for _rid, _had, _budget, pos in span.slots:
            o, b = cost(pos + j + 1, cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
            ops, nbytes = ops + o, nbytes + b
        yield ops * n, nbytes * n
