"""Share of the decode rows computed that made a token a request kept
(%): the engine's `decode_tokens_kept` over its `decode_slot_steps`
(steps x batch rows). Nothing where the program keeps no such counters."""


def read(ctx):
    c = ctx["summary"].get("counters", {})
    rows = c.get("decode_slot_steps")
    if not rows:
        return None
    return 100.0 * c["decode_tokens_kept"] / rows
