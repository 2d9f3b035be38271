"""Model step, decode: the operations the decode tokens in the traced part
of the window needed (``work.decode_flops`` at each token's context), over
the device time of the decode programs (``jit_decode_loop``) times the
chip's bf16 peak, in percent.  Idle slots and padded steps add device
time and no operations."""

PROGRAM = "decode_loop"


def read(ctx):
    if ctx.trace is None or not ctx.traced_work["decode_tokens"]:
        return None
    t = ctx.trace.program_s(PROGRAM)
    if t <= 0:
        return None
    return 100.0 * ctx.traced_work["decode_flops"] / (
        t * ctx.peaks.bf16_flops_per_s)
