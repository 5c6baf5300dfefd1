"""The engine's prefill wall time per thousand prompt tokens prefilled."""


def read(ctx):
    toks = sum(a.prompt_len for a in ctx["sched"].admissions)
    return 1e6 * ctx["summary"]["prefill_s"] / toks if toks else None
