"""How many of the program's compilations in set-up JAX's persistent
cache did not serve (``repro.runtime.obs``), counting only functions whose
tracing entered the program's own spans: 0 when every program was loaded
from the cache, 1 or more when XLA compiled.  None for a program that
records no such compilation.

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
    return c["cache_misses"] if c["compiles"] else None
