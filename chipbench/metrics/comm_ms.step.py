"""Device milliseconds per step, averaged over the chips, of the
collective operations the program emits: all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute, whole or in their
``-start``/``-done`` halves, matched by the opcode at the head of the HLO
instruction name that each device op carries.  0 where the program emits
none; None where the trace holds no device op."""
import re

_COLLECTIVE = re.compile(r"^(?:all-reduce|all-gather|reduce-scatter|"
                         r"all-to-all|collective-permute)(?:-[a-z]+)*"
                         r"(?:\.\d+)?$")


def read(r):
    ops = r.trace.ops
    if not ops:
        return None
    t = sum(o.dur_ns for o in ops if _COLLECTIVE.match(o.name))
    return 1e3 * r.per_step(t * 1e-9 / max(1, len(r.trace.devices)))
