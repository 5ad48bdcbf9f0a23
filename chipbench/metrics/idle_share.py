"""The device's idle share of the traced window, in percent: one minus the
union of its program executions over the window's length."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
