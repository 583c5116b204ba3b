"""Host milliseconds of the program's own spans during set-up: the sum of
their self times (``repro.runtime.obs``), which cover op-bundle builds,
kernel-configuration resolution, the Python tracing of the projector
wrappers and their Pallas bodies, and FBP's host tables.  None for a
program without such spans.

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
    spans = obs.snapshot()["spans"]
    if not spans:
        return None
    return 1e3 * sum(s["self_s"] for s in spans.values())
