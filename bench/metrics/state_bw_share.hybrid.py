"""Recurrent state moved by decode as a share of the chip's HBM bandwidth
(%): the engine's `decode_state_bytes` (each decode step reads and writes
every live slot's ssm and conv state) over its decode wall time
(`decode_s`), over the peak bytes per second. Nothing where the program
keeps no such counter."""


def read(ctx):
    c = ctx["summary"].get("counters", {})
    nbytes, t = c.get("decode_state_bytes"), ctx["summary"]["decode_s"]
    if not nbytes or t <= 0:
        return None
    return 100.0 * nbytes / t / ctx["peaks"]["hbm_bytes_per_s"]
