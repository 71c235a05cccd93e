"""Device ms per decode step in ops of the ``moe_route`` scope
(``models/moe.moe_block``): the router's matmul, softmax and top-k, the
sort of the routed rows by expert and their gather, and the weighted
combine of the experts' results.  The trace names ops alone; the compiled
step's text maps them to the scope."""
import devtrace as trace

SCOPE = "moe_route"


def read(ctx):
    rec, steps = ctx["trace"], ctx["counts"].get("decode_steps")
    if not steps or not rec["devices"]:
        return None
    names = trace.scoped_instructions(ctx["hlo"](), SCOPE)
    secs = trace.op_seconds(rec, lambda name: name in names)
    if secs <= 0:
        return None
    return 1e3 * secs / steps
