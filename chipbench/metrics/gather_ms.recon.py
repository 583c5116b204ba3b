"""Device milliseconds per reconstruction in gather ops (or fusions that
hold one) inside the harness's ``fbp`` scope: the backprojector's
interpolation."""


def read(r):
    t = r.trace.time_s(["fbp"], kind="gather")
    return 1e3 * r.per_step(t) if t > 0 else None
