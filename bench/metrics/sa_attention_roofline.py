"""The flash prefill kernel's share of its roofline in the traced
prefills (%): least time for the causal attention FLOPs of the real prompt
lengths (compute-bound), over the kernel's device time."""
from bench import work

# the kernel's custom call, named after its jitted wrapper: on a TPU v5e
# trace the event is the HLO instruction, "%_name.N = <shape> custom-call(..."
KERNEL = r"^%?sa_attention(\.\d+)?( |$)|jit\(sa_attention\)/pallas_call"


def read(ctx):
    span = ctx.get("trace_span")
    if span is None:
        return None
    return work.roofline(
        ctx, KERNEL, work.prefill_flops(ctx, span, attention_only=True),
        work.prefill_bytes(ctx, span))
