"""Device ms per decode step in ops of the ``decode_attn_interior`` scope:
the int8 dequant and the attention over the cache.  The trace names ops
alone; the compiled step's text maps them to the scope."""
import devtrace as trace

SCOPE = "decode_attn_interior"


def read(ctx):
    rec, steps = ctx["trace"], ctx["counts"].get("decode_steps")
    if not steps or not rec["devices"]:
        return None
    names = trace.scoped_instructions(ctx["hlo"](), SCOPE)
    secs = trace.op_seconds(rec, lambda name: name in names)
    if secs <= 0:
        return None
    return 1e3 * secs / steps
