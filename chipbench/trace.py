"""Reduction of a profiler trace to the benchmark's per-layer readings.

The traced run writes an ``.xplane.pb``; :func:`read` loads it with
``jax.profiler.ProfileData`` and keeps:

* the device operations (line "XLA Ops" of each "/device:TPU:<n>" plane;
  the trace runs over the window alone, so every one belongs to it);
* the harness's host spans (``chipbench.*`` TraceAnnotations): the window
  span gives the window's length on the host clock, and the others say
  what the host was doing in each idle gap.

The device clock is not the host clock: device times are placed on the
host's by lining up the last device operation's end with the window
span's end, which is only used to name the idle gaps.

Each operation is classified from the compiled program's HLO text, not
from its name: its harness scope (the first ``jax.named_scope`` of the
harness found in the op's ``op_name`` metadata) and its kind - "pallas"
for a Mosaic custom call, "gather" for a gather or a fusion that holds
one, "loop" for a while, conditional or call (its body's ops are events
of their own, so its time is not summed again), "other" for every other
XLA op.  A renamed kernel or fusion is thus classified the same way.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
HOST_PREFIX = "chipbench."

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclasses.dataclass(frozen=True)
class HloOp:
    opcode: str
    op_name: str
    kind: str            # "pallas" | "gather" | "loop" | "other"


def hlo_index(hlo_text: str) -> Dict[str, HloOp]:
    """Instruction name -> (opcode, op_name metadata, kind)."""
    raw: Dict[str, Tuple[str, str, Optional[str], bool]] = {}
    comp_ops: Dict[str, set] = defaultdict(set)
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith((" ", "\t")):
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        body = rest.split(", metadata=")[0]
        op = _OPCODE.search(body)
        if not op:
            continue
        opcode = op.group(1)
        comp_ops[comp].add(opcode)
        calls = _CALLS.search(body)
        on = _OPNAME.search(rest)
        raw[name] = (opcode, on.group(1) if on else "",
                     calls.group(1) if calls else None,
                     'custom_call_target="tpu_custom_call"' in body)
    out = {}
    for name, (opcode, op_name, calls, mosaic) in raw.items():
        if mosaic:
            kind = "pallas"
        elif opcode in ("while", "conditional", "call"):
            kind = "loop"
        elif opcode == "gather" or (opcode == "fusion" and calls
                                    and "gather" in comp_ops.get(calls, ())):
            kind = "gather"
        else:
            kind = "other"
        out[name] = HloOp(opcode, op_name, kind)
    return out


def scope_of(op_name: str, scopes: Sequence[str]) -> str:
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return ""


@dataclasses.dataclass
class Op:
    name: str
    scope: str
    kind: str
    start_ns: float
    dur_ns: float
    device: str


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                  # ns, host clock
    ops: List[Op]                                # ns, device clock
    host: List[Tuple[str, float, float]]          # (name, start_ns, end_ns)
    devices: List[str]
    offset_ns: float = 0.0                        # device + offset = host

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(_union([(o.start_ns, o.start_ns + o.dur_ns)
                           for o in self.ops if o.device == d])
                   for d in self.devices) * 1e-9 / len(self.devices)

    def time_s(self, scopes: Iterable[str] = None, kind: str = None,
               not_kind: str = None) -> float:
        """Summed device time of the matching ops, averaged over devices."""
        scopes = None if scopes is None else set(scopes)
        t = sum(o.dur_ns for o in self.ops
                if o.kind != "loop"
                and (scopes is None or o.scope in scopes)
                and (kind is None or o.kind == kind)
                and (not_kind is None or o.kind != not_kind))
        return t * 1e-9 / max(1, len(self.devices))

    def top_ops(self, n: int = 10) -> List[list]:
        """The device operations that took most time, by HLO name."""
        tot: Dict[str, float] = defaultdict(float)
        for o in self.ops:
            if o.kind == "loop":
                continue
            label = "/".join(x for x in (o.scope, o.kind, o.name) if x)
            tot[label] += o.dur_ns * 1e-9 / max(1, len(self.devices))
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest idle gaps of the first device in the window, each
        named by the host span that covers most of it."""
        if not self.devices:
            return []
        d, off = self.devices[0], self.offset_ns
        busy = _merge([(o.start_ns + off, o.start_ns + o.dur_ns + off)
                       for o in self.ops if o.device == d])
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, cover = "host:none", 0.0
            for name, hs, he in self.host:
                if name == WINDOW:
                    continue
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name[len(HOST_PREFIX):], c
            out.append([best, (e - s) * 1e-9])
        return out


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _union(iv) -> float:
    return sum(e - s for s, e in _merge(iv))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str, hlo: Dict[str, HloOp], scopes: Sequence[str]) -> Trace:
    """Load the trace at ``path`` (an ``.xplane.pb``, or one gzipped)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    host, dev_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_lines.append((plane.name, line))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    ops = []
    for dev, line in dev_lines:
        for ev in line.events:
            m = _EVENT.match(ev.name)
            name = m.group(1) if m else ev.name
            h = hlo.get(name)
            ops.append(Op(name, scope_of(h.op_name, scopes) if h else "",
                          h.kind if h else "other", ev.start_ns,
                          ev.duration_ns, dev))
    end = max((o.start_ns + o.dur_ns for o in ops), default=wins[0][1])
    devices = sorted({d for d, _ in dev_lines})
    return Trace(wins[0], ops, host, devices, wins[0][1] - end)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets: the reduced trace, the steps
    completed in the traced window, the step's work per call
    (:mod:`chipbench.work`) and the chip's peaks."""
    trace: Trace
    steps: int
    work: dict
    peak: dict

    def per_step(self, seconds: float) -> float:
        return seconds / self.steps

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.trace.busy_s() / self.trace.window_s)
