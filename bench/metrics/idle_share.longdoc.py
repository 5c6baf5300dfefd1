"""Share of the traced window in which no operation ran on the device (%)."""
from bench import trace_reduce as R


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr["devices"]:
        return None
    lo, hi = R.window(tr)
    return 100.0 * (1.0 - R.busy_s(tr) / ((hi - lo) / 1e9))
