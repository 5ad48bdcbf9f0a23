"""The exact cloud scan's share of its roofline, in percent: the least
time the chip needs for the window's scans (``work.scan_work``: the f32
corpus once per call, 2 * rows * N * d flops) over the device time inside
the ``cloud_scan`` spans of the traced run.  Nothing to read without that
device time (a window with no rejected draft)."""
from chipbench import work


def read(ctx):
    if ctx.trace is None or not ctx.scan_calls:
        return None
    w = work.scan_work(ctx.config["n_docs"], ctx.config["d"], ctx.scan_calls,
                       ctx.scan_rows)
    return work.roofline_pct(
        w, ctx.trace.span_device_s.get("cloud_scan", 0.0), ctx.peaks)
