"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
the program finished, the longest among them, is run through the float32
reference (``bench/reference.py``) over each prompt with its served
tokens.  The number compared is the widest gap by which a served token's
logit lies below the reference's best logit at that position: greedy
serving at the configuration's precision keeps it small, and anything that
alters a token, drops a state update or serves at a lower precision moves
it up.  Its limit is the configuration file's ``limits.logit_gap``.
"""
from __future__ import annotations

import numpy as np

from bench import reference

MIN_TOKENS = 1024       # served tokens compared, at least ...
MIN_REQUESTS = 4        # ... over at least this many requests
MAX_REQUESTS = 8


def sample(outcomes: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until both minimums are met."""
    ok = [o for o in outcomes if o.status == "ok" and o.tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda o: (len(o.tokens), -o.rid))
    rest = [o for o in ok if o is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    pick, n = [longest], len(longest.tokens)
    for i in order:
        if len(pick) >= MAX_REQUESTS or (len(pick) >= MIN_REQUESTS
                                          and n >= MIN_TOKENS):
            break
        pick.append(rest[i])
        n += len(rest[i].tokens)
    return pick


def logit_gap(cfg: dict, seed: int, picked: list, prompts: dict,
              max_len: int) -> tuple:
    """(widest gap, served tokens compared) over the picked requests;
    ``max_len`` is the longest prompt plus answer the cell can serve."""
    seqs = [np.concatenate([prompts[o.rid], np.asarray(o.tokens, np.int32)])
            for o in picked]
    starts = [len(prompts[o.rid]) for o in picked]
    g = reference.gaps(cfg, seed, seqs, starts, rows=MAX_REQUESTS,
                       length=max_len)
    return float(max(x.max() for x in g)), int(sum(len(x) for x in g))
