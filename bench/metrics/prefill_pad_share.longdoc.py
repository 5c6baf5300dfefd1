"""Share of the prefilled positions that are bucket padding (%): the
engine's `prefill_positions` (after bucketing) less its `prefill_tokens`
(real prompt tokens), over `prefill_positions`. Nothing where the program
keeps no such counters."""


def read(ctx):
    c = ctx["summary"].get("counters", {})
    pos = c.get("prefill_positions")
    if not pos:
        return None
    return 100.0 * (pos - c["prefill_tokens"]) / pos
