"""Share of the traced window in which no op ran on the device (%)."""
import devtrace as trace


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    return 100.0 * trace.idle_share(ctx["trace"])
