"""The stencil kernel's share of its roofline (%): least time of its passes
(bytes bound: every cell read and written once per pass, from ``counts.py``,
at the chip's HBM peak) over the kernel's device time in the trace."""
import devtrace as trace

KERNEL = "jacobi"


def read(ctx):
    c = ctx["counts"]
    secs = trace.op_seconds(ctx["trace"], lambda name: KERNEL in name)
    if not c.get("passes") or secs <= 0:
        return None
    least = c["passes"] * c["pass_bytes"] / ctx["peaks"].hbm_bytes
    return 100.0 * least / secs
