"""The train step's model FLOP utilization (%): model FLOPs per step
(``counts.train_step_flops``: 6 N T plus causal attention, no
recomputation) times steps, over the traced window, the chips and their
peak."""


def read(ctx):
    c = ctx["counts"]
    if not ctx["trace"]["devices"] or not c.get("steps"):
        return None
    window = ctx["trace"]["window_ns"] / 1e9
    return 100.0 * c["step_flops"] * c["steps"] / (
        window * ctx["chips"] * ctx["peaks"].flops)
