"""Host milliseconds of set-up spent lowering the program's jitted
functions to MLIR and compiling them with XLA or loading them from JAX's
persistent cache, counting only functions whose tracing entered the
program's own spans (``repro.runtime.obs``): the harness's input and
reference jits are left out.  None for a program that records no such
compilation.

Limit: the reader runs after the window and the check and reads the
whole table, not a snapshot taken at the end of set-up.  The name holds
while neither runs program code: the default window calls a compiled
step, which records nothing, and the checks run only the reference.  A
step kind with its own ``window()`` that calls the program (a served
cell, with per-request ``serve.*`` spans), or a check that does, would
fold its spans and compilations in here; such a cell needs ``run.py``
to snapshot the table at the end of set-up and hand that to the
readers."""


def read(r):
    try:
        from repro.runtime import obs
    except ImportError:
        return None
    c = obs.snapshot()["compile"]
    if not (c["lowerings"] or c["compiles"]):
        return None
    return 1e3 * (c["lower_s"] + c["backend_s"])
