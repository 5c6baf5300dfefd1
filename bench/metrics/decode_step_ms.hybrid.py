"""The engine's decode wall time over the decode steps it ran (ms)."""


def read(ctx):
    steps = sum(c.steps for c in ctx["sched"].chunks)
    return 1e3 * ctx["summary"]["decode_s"] / steps if steps else None
