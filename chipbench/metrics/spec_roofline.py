"""Speculation's share of its roofline, in percent: the least time the
chip needs for the speculation calls of the window (``work.spec_work``:
centroids, validation table and doc store once per call, the probed
buckets once per query) over the device time inside the ``spec`` spans of
the traced run.  Nothing to read without that device time."""
from chipbench import work


def read(ctx):
    if ctx.trace is None:
        return None
    w = work.spec_work(ctx.config["has"], ctx.config["d"], ctx.spec_calls,
                       ctx.spec_rows)
    return work.roofline_pct(w, ctx.trace.span_device_s.get("spec", 0.0),
                             ctx.peaks)
