"""The decode steps' share of the chip's peak (%): the least time of each
step of the traced window (FLOPs or bytes, whichever binds, from
``counts.py``) summed, over the traced window."""


def read(ctx):
    c = ctx["counts"]
    if not ctx["trace"]["devices"] or not c.get("decode_steps"):
        return None
    return 100.0 * c["least_s"] / (ctx["trace"]["window_ns"] / 1e9)
