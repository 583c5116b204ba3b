"""FP kernels' share of the roofline: the least time the FP call's work
needs on this chip (:mod:`chipbench.work`) over the device time of the
Pallas kernels in the harness's ``fp`` scope, per step."""
from chipbench.work import roofline_share


def read(r):
    t = r.per_step(r.trace.time_s(["fp"], kind="pallas"))
    if t <= 0 or "fp" not in r.work:
        return None
    return roofline_share(r.work["fp"], t, r.peak)[0]
