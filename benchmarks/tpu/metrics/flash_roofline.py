"""The flash attention kernels' share of their roofline (%): the least time
of the forward, dk/dv and dq kernel calls of the traced steps (FLOPs or
bytes, whichever binds, from ``counts.py``) over the kernels' device time.
Each call is found by its kernel's function name in the compiled step
(``devtrace.kernel_instructions``)."""
import devtrace as trace

KERNELS = {"fwd": "_fwd_kernel", "dkv": "_bwd_dkv_kernel", "dq": "_bwd_dq_kernel"}


def read(ctx):
    c = ctx["counts"]
    rec = ctx["trace"]
    if not c.get("steps") or not rec["devices"]:
        return None
    hlo = ctx["hlo"]()
    least = secs = 0.0
    for kind, kernel in KERNELS.items():
        names = trace.kernel_instructions(hlo, kernel)
        evs = [e for evs in rec["devices"].values() for e in evs
               if e[0] in names]
        if not evs:
            return None
        calls = len(evs) / len(rec["devices"])
        least += calls * c["flash"][kind]
        secs += trace.op_seconds(rec, lambda n: n in names)
    return 100.0 * least / secs
