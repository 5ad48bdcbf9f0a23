"""Draft acceptance rate: requests whose draft was accepted (by the
speculation program, late re-validation or homology sharing), in percent
of the requests the window served.  A count read from the served flags."""


def read(ctx):
    if not ctx.requests:
        return None
    return 100.0 * ctx.accepted / ctx.requests
