"""Share of the decode slots' time spent stalled behind admissions (%):
the engine's `decode_stall_s` (the admissions made while another slot was
live) over itself plus the decode chunks' time (the `decode_chunk` spans).
Nothing where the program records no spans."""


def read(ctx):
    s = ctx["summary"]
    chunks = s.get("spans", {}).get("decode_chunk")
    if chunks is None:
        return None
    t = s["decode_stall_s"] + chunks["s"]
    return 100.0 * s["decode_stall_s"] / t if t > 0 else None
