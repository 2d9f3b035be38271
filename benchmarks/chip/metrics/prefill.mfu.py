"""Model step, prefill: the operations the prefills in the traced part of
the window needed (``work.prefill_flops`` of each true prompt length, the
head at the last position only), over the device time of the prefill
programs (``jit_prefill_pack``) times the chip's bf16 peak, in percent."""

PROGRAM = "prefill_pack"


def read(ctx):
    if ctx.trace is None or not ctx.traced_work["prefills"]:
        return None
    t = ctx.trace.program_s(PROGRAM)
    if t <= 0:
        return None
    return 100.0 * ctx.traced_work["prefill_flops"] / (
        t * ctx.peaks.bf16_flops_per_s)
