"""Device ms per train step in ops of the ``adamw_update`` scope
(``optim/adamw.update``): the gradients' global norm, the clip and the
update of parameters and moments.  The trace names ops alone; the compiled
step's text maps them to the scope.  A fusion that holds an op of the scope
counts whole: XLA fuses part of the norm's sum of squares into a matmul
fusion of the backward pass, which then counts too."""
import devtrace as trace

SCOPE = "adamw_update"


def read(ctx):
    rec, steps = ctx["trace"], ctx["counts"].get("steps")
    if not steps or not rec["devices"]:
        return None
    names = trace.scoped_instructions(ctx["hlo"](), SCOPE)
    secs = trace.op_seconds(rec, lambda name: name in names)
    if secs <= 0:
        return None
    return 1e3 * secs / steps
