"""Output tokens per second of a cut window.  The window closes to new
work after ``--seconds``: no admission or decode block starts after that,
and the window ends when the last one begun before it has put its tokens
on the host.  Every token on the host by then counts, those of requests
still in flight too, over the window's whole length.  Ending at the
nominal second instead would count the block in flight whole or not at
all: a step of 64 x 8 tokens, 1.5% of granite-8b's rate."""


def read(run):
    spans = run.rec.admits + run.rec.blocks
    if not spans:
        return None
    end = max(s.t1 for s in spans)
    n = sum(steps[-1][1] for steps in run.token_times().values())
    return n / (end - run.rec.t_start)
