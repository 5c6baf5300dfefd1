"""The fused paged decode kernel's share of its roofline in the traced
steps (%): least time to read the K and V of every live position of every
kept decode token (memory-bound), over the kernel's device time."""
from bench import work

# the kernel's custom call, named after its jitted wrapper: on a TPU v5e
# trace the event is the HLO instruction, "%_name.N = <shape> custom-call(..."
KERNEL = r"^%?_paged_decode(\.\d+)?( |$)|jit\(_paged_decode\)/pallas_call"


def read(ctx):
    span = ctx.get("trace_span")
    if span is None:
        return None
    return work.roofline(ctx, KERNEL, work.decode_attention_flops(ctx, span),
                         work.decode_kv_bytes(ctx, span))
