"""Model step, prefill: share of the prefill programs' (``jit_prefill_pack``)
leaf-op device time in the traced part that ran under the ``lm_head``
named scope, in percent; None where the trace holds no such program or
the program opens no such scope."""

PROGRAM = "jit_prefill_pack"
SCOPE = "lm_head"


def read(ctx):
    d = (ctx.scope_s or {}).get(PROGRAM)
    if not d or d["leaf_s"] <= 0 or SCOPE not in d["under"]:
        return None
    return 100.0 * d["under"][SCOPE] / d["leaf_s"]
