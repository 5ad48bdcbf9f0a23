"""The program's own host time a request, in ms: the device-idle time
inside the ``has.step`` spans of the traced window (each idle interval
credited to the innermost program span over it), over the requests.  The
harness loop's time outside ``has.step`` is left out.  Nothing to read from
a program without those spans."""
from chipbench import program_spans


def read(ctx):
    pt = program_spans.read(ctx)
    return None if pt is None else pt.step_host_ms
