"""Device ms per decode step in the grouped expert matmuls of
``models/moe.moe_block``: ops of the ``moe_experts`` scope and the kernels
that XLA emits for its ``jax.lax.ragged_dot`` calls.  On a TPU those are
custom calls named ``ragged-dot-*`` (the kernel and its group metadata)
whose op_name XLA rewrites, so they carry no scope; nothing else in the
program calls ``ragged_dot``.  The trace names ops alone; the compiled
step's text maps them to the scope."""
import devtrace as trace

SCOPE = "moe_experts"
KERNEL = "ragged-dot"


def read(ctx):
    rec, steps = ctx["trace"], ctx["counts"].get("decode_steps")
    if not steps or not rec["devices"]:
        return None
    names = trace.scoped_instructions(ctx["hlo"](), SCOPE)
    secs = trace.op_seconds(rec, lambda n: n in names or n.startswith(KERNEL))
    if secs <= 0:
        return None
    return 1e3 * secs / steps
