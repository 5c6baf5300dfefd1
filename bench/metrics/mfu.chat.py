"""Model FLOP/s of the prefills and decode tokens run, over the engine's
prefill + decode wall time, as a share of the chip's peak (%)."""
from bench import work


def read(ctx):
    return work.mfu(ctx)
