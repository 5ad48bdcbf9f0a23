"""The sharing election's device time a speculation call, in ms: the
device time inside the ``share`` spans of the traced run (the scheduler's
``intra_batch_share``, once per speculation batch) over the window's
speculation calls.  Nothing to read on a path without that span, or from a
trace without a device plane."""


def read(ctx):
    if ctx.trace is None or not ctx.spec_calls:
        return None
    s = ctx.trace.span_device_s.get("share")
    return None if s is None else 1e3 * s / ctx.spec_calls
