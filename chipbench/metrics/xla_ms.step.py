"""Device milliseconds per step of the XLA ops (everything but the Pallas
kernels) inside the harness's ``fp`` and ``bp`` scopes: the kernel
wrappers' transposes, padding and axial contraction."""


def read(r):
    t = r.trace.time_s(["fp", "bp"], not_kind="pallas")
    if not any(o.scope in ("fp", "bp") for o in r.trace.ops):
        return None
    return 1e3 * r.per_step(t)
