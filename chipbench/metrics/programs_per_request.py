"""Device programs a request: the program executions of the traced window
(``XLA Modules`` events) that start inside a ``has.step`` span, over the
requests.  Explicit launches and the eager ops between them (indexing,
gathers, uploads) alike.  Nothing to read from a program without those
spans."""
from chipbench import program_spans


def read(ctx):
    pt = program_spans.read(ctx)
    return None if pt is None else pt.programs / pt.requests
