"""Model step, decode: leaf-op device seconds under the ``lm_head`` named
scope in the decode programs (``jit_decode_loop``) of the traced part,
over the decode steps that ran there; None where the trace holds no such
program or step, or the program opens no such scope."""

PROGRAM = "jit_decode_loop"
SCOPE = "lm_head"


def read(ctx):
    d = (ctx.scope_s or {}).get(PROGRAM)
    steps = (ctx.traced_work or {}).get("decode_steps")
    if not d or not steps or SCOPE not in d["under"]:
        return None
    return d["under"][SCOPE] / steps
