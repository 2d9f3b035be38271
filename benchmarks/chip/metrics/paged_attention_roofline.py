"""Kernel: the paged attention kernel's share of its roofline in the
traced part of the window.  The least time the chip could take is the
larger of the K/V bytes that the kernel reads from HBM over HBM bandwidth
and the kernel's operations over the bf16 peak; the share is that over the
kernel's device time, in percent.

The decode tokens need ``work.kv_read_bytes`` at each token's context, K
and V alike.  Of the two pool operands of the call (table, positions, q,
pool K, pool V), only those that the compiled program leaves in HBM are
read from it by the kernel: one placed in the core's VMEM (``S(1)`` in the
op's HLO text) was filled there by an op before the kernel, and its reads
are not HBM traffic.  Where the kernel's instances differ, the least HBM
share among them is taken.

The kernel's ops are those named ``paged_attention``; while no Pallas call
in the program carries a name, they are its Pallas calls
(``tpu_custom_call``): the served path has no other."""
from trace_reduce import operand_spaces

KERNEL = "paged_attention"
PALLAS = "tpu_custom_call"
POOL_OPERANDS = (3, 4)


def hbm_share(texts) -> float:
    """Least share of the pool operands that stay in HBM (space 0)."""
    shares = []
    for text in texts:
        spaces = operand_spaces(text)
        if len(spaces) <= max(POOL_OPERANDS):
            raise ValueError(f"not the paged attention call: {text[:200]}")
        shares.append(sum(spaces[i] == 0 for i in POOL_OPERANDS)
                      / len(POOL_OPERANDS))
    return min(shares)


def read(ctx):
    if ctx.trace is None or not ctx.traced_work["decode_tokens"]:
        return None
    name = KERNEL if ctx.trace.op_s(KERNEL) > 0 else PALLAS
    t = ctx.trace.op_s(name)
    if t <= 0:
        return None
    w, p = ctx.traced_work, ctx.peaks
    least = max(w["kv_bytes"] * hbm_share(ctx.trace.op_texts(name))
                / p.hbm_bytes_per_s,
                w["kernel_flops"] / p.bf16_flops_per_s)
    return 100.0 * least / t
