"""Operations and bytes of ``prefill_attention``: one chunk of queries
against the int8 K/V of every position up to the chunk's end, causal.

Counted is the work the chunk needs: only the prompt's real tokens as
queries and keys (an admission pads the prompt to its cap, and the
padded chunks need nothing), 4 * head_dim operations per query head and
key (scores and values), and each key and value read once as int8.
Its rate is the bf16 peak.
"""
OPS_PEAK = "bf16_flops"


def cost(q_rows: int, kv_len: int, pairs: int, heads: int, kv_heads: int,
         head_dim: int) -> tuple:
    """(operations, bytes) of one call: ``pairs`` (query, key) pairs in
    causal order, ``kv_len`` keys read; f32 queries in and out."""
    ops = 4 * pairs * heads * head_dim
    nbytes = 2 * kv_len * kv_heads * head_dim + 2 * 4 * q_rows * heads * head_dim
    return ops, nbytes


def calls(run, kind: str, span):
    if kind != "admit":
        return
    cfg, chunk = run.cfg, run.server["chunk"]
    length = span.prompt_len
    for c0 in range(0, length, chunk):
        rows = min(chunk, length - c0)
        # query at position p attends keys 0..p
        pairs = rows * c0 + rows * (rows + 1) // 2
        ops, nbytes = cost(rows, c0 + rows, pairs,
                           cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"])
        n = cfg["num_hidden_layers"]
        yield ops * n, nbytes * n
