"""Share of the device's busy time in the traced decode blocks that
``expert_matmul`` takes: its events' device time over the busy time
inside those blocks.  None without a trace or without the kernel."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    blocks = run.spans("decode", traced=True)
    kernel = run.trace.kernel_seconds("expert_matmul", blocks)
    busy = 0.0
    for b in blocks:
        se = run.trace.spans.get(("decode", b.seq))
        if se is not None:
            until = spans.busy_until(run.trace.busy, se)
            busy += float(until[1] - until[0]) * 1e-9
    if not kernel or not busy:
        return None
    return 100.0 * kernel / busy
