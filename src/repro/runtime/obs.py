"""The program's spans and counters: where its host time goes, and which
of its programs JAX compiled.

* ``span(name, **attrs)`` times a block of host code.  Under a running
  ``jax.profiler`` trace the block is also a ``TraceAnnotation`` of the
  same name, on the profiler's host clock.  In memory every name keeps a
  count, its total seconds and its self seconds (the total less what its
  child spans, and the lowerings and compilations run inside it, cover),
  and a bounded ring keeps the recent records: name, start, end, id, the
  id of the span it opened in, and ``attrs`` (a served request's spans
  carry its ``rid``).  ``spanned(name)`` wraps a function in one.
* ``record(name, start, end, **attrs)`` adds a span timed elsewhere on
  ``time.perf_counter`` (a request's wait in a queue, say).
* ``count(name, n=1)`` and ``counter(name)``.
* ``snapshot()`` returns the table as a dict; ``dump(path)`` writes it as
  JSON.

Compilations.  On import, listeners on ``jax.monitoring`` follow each
function JAX traces, lowers to an MLIR module and compiles (or loads
from the persistent compilation cache).  A lowering or compilation
counts as the system's when a span opened while its function was being
traced: jits of code that never enters a span of this program, such as
a caller's own reference or input generator, are never counted.  Each
counted program keeps its lowerings and seconds, its compilations and
seconds, and how many were loaded from the persistent cache (hits) or
compiled by XLA (misses; with the cache off every compilation is one).
Every other lowering and compilation is only summed, as ``other``.
A program is matched to its last trace by its function's name, and a
compilation to the last lowering of the same module name.

Always on, with no switch: spans sit only at host-level build, trace
and dispatch points, never inside jitted code, so running a compiled
program records nothing.  ``jax.clear_caches()`` leaves the table alone.
"""
from __future__ import annotations

import collections
import functools
import itertools
import json
import threading
import time
from typing import Any, Dict, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

__all__ = ["Recorder", "span", "spanned", "record", "count", "counter",
           "snapshot", "dump"]

RING = 4096          # recent records kept

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_STAGES = (_TRACE, _LOWER, _COMPILE)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_PROGRAM = ("lowerings", "lower_s", "compiles", "backend_s", "cache_hits",
            "cache_misses", "cache_read_s")       # per counted program


class _Stage:
    """One trace, lowering or compilation JAX has under way on a thread."""
    __slots__ = ("event", "fun", "system", "hit", "read_s")

    def __init__(self, event: str, fun: str, system: bool):
        self.event, self.fun, self.system = event, fun, system
        self.hit, self.read_s = False, 0.0


class _Thread(threading.local):
    def __init__(self):
        self.spans = []          # open spans, innermost last
        self.stages = []         # JAX stages under way, innermost last


class _Span:
    __slots__ = ("_rec", "name", "attrs", "id", "parent", "start", "end",
                 "child_s", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec, self.name, self.attrs = rec, name, attrs
        self.child_s = 0.0
        self.end = None

    def __enter__(self) -> "_Span":
        th = self._rec._thread
        self.parent = th.spans[-1].id if th.spans else None
        self.id = next(self._rec._ids)
        for st in th.stages:
            if st.event == _TRACE:
                st.system = True
        th.spans.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        th = self._rec._thread
        th.spans.pop()
        dur = self.end - self.start
        if th.spans:
            th.spans[-1].child_s += dur
        self._rec._add(self.name, dur, dur - self.child_s, self.id,
                       self.parent, self.start, self.end, self.attrs)

    @property
    def seconds(self) -> float:
        """The span's duration, once it has closed."""
        return self.end - self.start


def _inner(module: str) -> str:
    """``jit(step)`` -> ``step``: the traced function of a module name."""
    i = module.find("(")
    return module[i + 1:-1] if i >= 0 and module.endswith(")") else module


class Recorder:
    """One table of spans, counters and compilations.  The module keeps
    one for the process; a test may make its own and install it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._thread = _Thread()
        self._ids = itertools.count(1)
        self._spans: Dict[str, list] = {}        # name -> [n, total, self]
        self._counters: Dict[str, int] = collections.Counter()
        self._programs: Dict[str, Dict[str, float]] = {}
        self._other = dict.fromkeys(_PROGRAM[:4], 0)
        self._recent = collections.deque(maxlen=RING)
        self._traced: Dict[str, bool] = {}      # function -> held a span
        self._lowered: Dict[str, bool] = {}     # module -> the system's

    # -- spans and counters ------------------------------------------------ #
    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def record(self, name: str, start: float, end: float, **attrs) -> None:
        th = self._thread
        parent = th.spans[-1].id if th.spans else None
        self._add(name, end - start, end - start, next(self._ids), parent,
                  start, end, attrs)

    def _add(self, name, total, self_s, sid, parent, start, end, attrs):
        with self._lock:
            row = self._spans.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += total
            row[2] += self_s
            self._recent.append({"id": sid, "parent": parent, "name": name,
                                 "start": start, "end": end, "attrs": attrs})

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def counter(self, name: str) -> int:
        return self._counters.get(name, 0)

    # -- JAX's stages ------------------------------------------------------ #
    def _stage_start(self, event: str, fun: str) -> None:
        if event == _LOWER:
            system = self._traced.get(_inner(fun), False)
        elif event == _COMPILE:
            system = self._lowered.get(fun, False)
        else:
            system = False
        self._thread.stages.append(_Stage(event, fun, system))

    def _stage(self) -> Optional[_Stage]:
        st = self._thread.stages
        return st[-1] if st else None

    def _stage_end(self, event: str, fun: str, secs: float) -> None:
        th = self._thread
        while th.stages:
            st = th.stages.pop()
            if st.event == event and st.fun == fun:
                break
        else:
            return
        if event == _TRACE:
            self._traced[fun] = st.system
            return
        if event == _LOWER:
            self._lowered[fun] = st.system
        if th.spans:                 # not the enclosing span's own work
            th.spans[-1].child_s += secs
        with self._lock:
            p = (self._programs.setdefault(fun, dict.fromkeys(_PROGRAM, 0))
                 if st.system else self._other)
            if event == _LOWER:
                p["lowerings"] += 1
                p["lower_s"] += secs
            else:
                p["compiles"] += 1
                p["backend_s"] += secs
            if event == _COMPILE and st.system:
                p["cache_hits" if st.hit else "cache_misses"] += 1
                p["cache_read_s"] += st.read_s

    # -- reading ----------------------------------------------------------- #
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            spans = {k: {"count": n, "total_s": t, "self_s": s}
                     for k, (n, t, s) in self._spans.items()}
            programs = {k: dict(v) for k, v in self._programs.items()}
            other = dict(self._other)
            out = {"spans": spans, "counters": dict(self._counters),
                   "recent": [dict(r) for r in self._recent]}
        totals = {k: sum(p[k] for p in programs.values()) for k in _PROGRAM}
        out["compile"] = dict(totals, programs=programs, other=other)
        return out


_REC = Recorder()


def span(name: str, **attrs) -> _Span:
    """Context manager timing a block of host code as ``name``."""
    return _REC.span(name, **attrs)


def spanned(name: str):
    """Decorator: every call of the function is a span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _REC.span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def record(name: str, start: float, end: float, **attrs) -> None:
    """Add a span measured elsewhere (``time.perf_counter`` seconds)."""
    _REC.record(name, start, end, **attrs)


def count(name: str, n: int = 1) -> None:
    _REC.count(name, n)


def counter(name: str) -> int:
    return _REC.counter(name)


def snapshot() -> Dict[str, Any]:
    """``{"spans": {name: {count, total_s, self_s}}, "counters": {...},
    "compile": {totals..., "programs": {module: {...}}, "other": {...}},
    "recent": [...]}``"""
    return _REC.snapshot()


def dump(path) -> None:
    """Write :func:`snapshot` as JSON to ``path``."""
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=1, default=str)


# -- listeners on jax.monitoring -------------------------------------------- #
def _on_scalar(event: str, value: float, **kw) -> None:
    if event in _STAGES:                 # a stage's start time
        _REC._stage_start(event, kw.get("fun_name", ""))


def _on_duration(event: str, secs: float, **kw) -> None:
    if event in _STAGES:
        _REC._stage_end(event, kw.get("fun_name", ""), secs)
    elif event == _CACHE_READ:
        st = _REC._stage()
        if st is not None and st.event == _COMPILE:
            st.read_s += secs


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        st = _REC._stage()
        if st is not None and st.event == _COMPILE:
            st.hit = True


monitoring.register_scalar_listener(_on_scalar)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)
