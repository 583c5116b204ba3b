"""BP kernels' share of the roofline: the least time the BP call's work
needs on this chip (:mod:`chipbench.work`) over the device time of the
Pallas kernels in the harness's ``bp`` scope, per step."""
from chipbench.work import roofline_share


def read(r):
    t = r.per_step(r.trace.time_s(["bp"], kind="pallas"))
    if t <= 0 or "bp" not in r.work:
        return None
    return roofline_share(r.work["bp"], t, r.peak)[0]
