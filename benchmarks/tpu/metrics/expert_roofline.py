"""The grouped expert matmuls' share of their roofline (%): the least time
of the traced decode steps' gate, up and down grouped matmuls (FLOPs or
bytes, whichever binds, from ``counts_moe.expert_step``: 6 d ff per routed
row; each held expert's weights read once, the routed rows read and the
results written once) over the device time of the ``ragged-dot-*``
kernels that XLA emits for them on a TPU."""
import devtrace as trace

KERNEL = "ragged-dot"


def read(ctx):
    c, rec = ctx["counts"], ctx["trace"]
    if not c.get("expert_least_s") or not rec["devices"]:
        return None
    secs = trace.op_seconds(rec, lambda name: name.startswith(KERNEL))
    if secs <= 0:
        return None
    return 100.0 * c["expert_least_s"] / secs
