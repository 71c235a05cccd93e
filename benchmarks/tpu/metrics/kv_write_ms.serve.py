"""Device ms per decode step in ops of the ``kv_cache_write`` scope: the new
row's quantize and its write into the cache (``layers.update_cache``).  On a
TPU v5e the per-sequence scatter runs as serial ``while`` loops that carry
the scope themselves, so each loop's event counts whole.  The trace names
ops alone; the compiled step's text maps them to the scope."""
import devtrace as trace

SCOPE = "kv_cache_write"


def read(ctx):
    rec, steps = ctx["trace"], ctx["counts"].get("decode_steps")
    if not steps or not rec["devices"]:
        return None
    names = trace.scoped_instructions(ctx["hlo"](), SCOPE)
    secs = trace.op_seconds(rec, lambda name: name in names)
    if secs <= 0:
        return None
    return 1e3 * secs / steps
