"""From a profiler trace of the traced window to device seconds per engine
layer, per kernel and in all.

What a TPU v5e trace holds (JAX 0.9, read by hand from a run of this
benchmark): the device plane ``/device:TPU:<n>`` has an ``XLA Modules``
line, one event per program run named ``jit_engine(<fingerprint>)``, and an
``XLA Ops`` line, one event per executed HLO instruction (every loop
iteration again) whose name is the instruction's whole HLO text, ``%fusion.7
= s32[1024]{0} fusion(...), ...``; its stats hold only device offsets and
durations, no scope.  Host annotations (``bench/*``) are events of the host
plane's ``python3`` line, on the same clock.

* An op's time is its self time: nested events on one line (a loop and its
  body) are not counted twice.  Device time is clipped to the host
  annotation ``bench/window``; ``busy_s`` is the union of op intervals in
  it, averaged over the chips, and ``coverage`` that union over the union
  of the programs' spans, which falls below 1 where the trace lost op
  events (a program's ops cover all but a few microseconds of its span).
* An op's program is the ``XLA Modules`` event that encloses it.  Its scope
  is the ``named_scope`` path in its instruction's ``metadata={op_name=...}``
  in the compiled program's HLO text, found by the instruction name.  A
  Pallas kernel is an instruction whose ``custom_call_target`` is
  ``tpu_custom_call``; its name is the instruction's without the ``.<n>``.

A trace is read into plain :class:`Plane` / :class:`Line` / :class:`Event`
records (``load``, which keeps only what the reduction reads), so the
reduction runs on hand-built traces too.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench/window"
HOST_PREFIX = "bench/"

#: engine scope (first path component under ``dex/``) -> layer
SCOPE_LAYER = {
    "route": "descent", "descent": "descent", "route_back": "descent",
    "scan": "scan",
    "fused_a2a": "write", "apply": "write",
    "lat": "lat",
}
#: Pallas kernels -> the layer whose time they are part of; ``leaf_scan``
#: runs outside the ``dex/scan/h*`` scopes, after the hops have gathered
#: each lane's leaf window
KERNEL_LAYER = {"leaf_scan": "scan", "leaf_write": "write"}
_DEX = re.compile(r"(?:^|/)dex/([A-Za-z0-9_]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def instruction(event_name: str) -> str:
    """The HLO instruction name an ``XLA Ops`` event carries."""
    m = _EVENT_INSTR.match(event_name)
    return m.group(1) if m else event_name


def load(path: str) -> list:
    """The planes of an ``.xplane.pb`` file, with only what the reduction
    reads: device op and module events (ops by instruction name) and the
    host's ``bench/*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names = {}
    planes = []
    for p in data.planes:
        device = bool(DEVICE_PLANE.match(p.name))
        lines = []
        for ln in p.lines:
            if device and ln.name == OPS_LINE:
                evs = []
                for e in ln.events:
                    n = e.name
                    short = names.get(n)
                    if short is None:
                        short = names[n] = instruction(n)
                    evs.append(Event(short, e.start_ns, e.duration_ns))
            elif device and ln.name == MODULES_LINE:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in ln.events]
            elif not device:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in ln.events if e.name.startswith(HOST_PREFIX)]
            else:
                continue
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


@dataclasses.dataclass
class HloIndex:
    module: str
    op_name: dict          # instruction -> op_name
    kernel: dict           # instruction -> kernel name
    kind: dict             # instruction -> opcode, or custom-call target


def hlo_index(text: str) -> HloIndex:
    """Instruction names to ``op_name``, kernel and kind.  An instruction
    with no ``op_name`` of its own, such as the body of a loop the compiler
    made from a scatter, takes that of the instruction that calls its
    computation (the loop), and so on outwards."""
    m = _MODULE.search(text)
    op_name, kernel, kind = {}, {}, {}
    computation_of, caller = {}, {}
    comp = None
    for line in text.splitlines():
        hm = _COMPUTATION.match(line)
        if hm:
            comp = hm.group(1)
            continue
        im = _INSTR.match(line)
        if not im:
            continue
        name, rest = im.groups()
        computation_of[name] = comp
        for callee in _CALLEE.findall(rest):
            caller.setdefault(callee, name)
        om = _OP_NAME.search(rest)
        if om:
            op_name[name] = om.group(1)
        target = _TARGET.search(rest)
        what = target or _OPCODE.search(rest)
        kind[name] = what.group(1) if what else ""
        if target and target.group(1) == "tpu_custom_call":
            kernel[name] = re.sub(r"\.\d+$", "", name)
    for name in computation_of:
        chain, at = [], name
        while at is not None and at not in op_name and at not in chain:
            chain.append(at)
            at = caller.get(computation_of.get(at))
        if at in op_name:
            for c in chain:
                op_name[c] = op_name[at]
    return HloIndex(m.group(1) if m else "", op_name, kernel, kind)


def layer_of(op_name: str) -> str:
    m = _DEX.search(op_name or "")
    if not m:
        return "unscoped"
    return SCOPE_LAYER.get(m.group(1), "dex_other")


def _self_times(events):
    """``(event, self_ns)`` for the events of one line, nested ones
    subtracted from their parent."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    stack = []   # [event, child_ns]
    for e in evs:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            done, child = stack.pop()
            out.append((done, done.dur_ns - child))
        if stack and e.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] += e.dur_ns
        stack.append([e, 0.0])
    while stack:
        done, child = stack.pop()
        out.append((done, done.dur_ns - child))
    return out


def _merge(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a, b, lo, hi):
    return max(0.0, min(b, hi) - max(a, lo))


class _Spans:
    """Host spans by start time, to name what the host did in a gap."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]
        self.longest = max((e.dur_ns for e in self.spans), default=0.0)

    def label(self, a, b):
        """The span that overlaps ``[a, b)`` most."""
        best, label = 0.0, "host/other"
        i = bisect.bisect_left(self.starts, a - self.longest)
        while i < len(self.spans) and self.starts[i] < b:
            s = self.spans[i]
            t = _clip(s.start_ns, s.end_ns, a, b)
            if t > best:
                best, label = t, s.name
            i += 1
        return label


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    scope_s: dict            # engine layer -> device seconds (all chips)
    kernel_s: dict           # kernel -> device seconds (all chips)
    chips: int
    ops_s: dict              # "<program>:<layer>:<op_name>:<kind>" -> s
    gaps_s: dict             # host span over the idle gap -> seconds
    coverage: float = 1.0    # busy time over the programs' (``XLA
    #                          Modules``) spans: under 1 where the trace
    #                          lost op events

    def top_ops(self, k):
        return sorted(([n, s] for n, s in self.ops_s.items()),
                      key=lambda x: -x[1])[:k]

    def top_gaps(self, k):
        return sorted(([n, s] for n, s in self.gaps_s.items()),
                      key=lambda x: -x[1])[:k]


def reduce(planes, hlo: dict) -> Reduction:
    """``hlo`` maps a program's short name (``engine``, ``smo``) to its
    compiled HLO text."""
    idx = {k: hlo_index(v) for k, v in hlo.items()}
    by_module = {i.module: k for k, i in idx.items()}

    host = [e for p in planes if not DEVICE_PLANE.match(p.name)
            for ln in p.lines for e in ln.events]
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = _Spans([e for e in host if e.name != WINDOW])

    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    scope_s = collections.Counter()
    kernel_s = collections.Counter()
    ops_s = collections.Counter()
    gaps_s = collections.Counter()
    names = {}
    busy_ns = 0.0
    modules_ns = 0.0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(OPS_LINE, Line(OPS_LINE, [])).events
        mods = sorted(lines.get(MODULES_LINE, Line(MODULES_LINE, [])).events,
                      key=lambda e: e.start_ns)
        mod_starts = [m.start_ns for m in mods]
        for ev, self_ns in _self_times(ops):
            t = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if t <= 0:
                continue
            t = (t / ev.dur_ns * self_ns if ev.dur_ns else 0.0) * 1e-9
            prog = _program(ev, mods, mod_starts, by_module) or "other"
            index = idx.get(prog)
            name = names.get(ev.name) or names.setdefault(
                ev.name, instruction(ev.name))
            kern = index.kernel.get(name) if index else None
            if prog == "engine":
                layer = KERNEL_LAYER.get(kern) or layer_of(
                    index.op_name.get(name, ""))
                scope_s[layer] += t
            else:
                layer = prog
            if kern:
                kernel_s[kern] += t
            if index:
                what = (index.op_name.get(name) or name) + ":" + \
                    index.kind.get(name, "")
            else:
                what = name
            ops_s[f"{prog}:{layer}:{what}"] += t
        merged = _merge([(max(e.start_ns, lo), min(e.end_ns, hi))
                         for e in ops if e.end_ns > lo and e.start_ns < hi])
        busy_ns += sum(b - a for a, b in merged)
        modules_ns += sum(b - a for a, b in _merge(
            [(max(m.start_ns, lo), min(m.end_ns, hi))
             for m in mods if m.end_ns > lo and m.start_ns < hi]))
        if plane is devices[0]:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps_s[spans.label(a, b)] += (b - a) * 1e-9
    chips = len(devices)
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns / chips * 1e-9,
        scope_s=dict(scope_s), kernel_s=dict(kernel_s), chips=chips,
        ops_s=dict(ops_s),
        gaps_s=dict(gaps_s),
        coverage=busy_ns / modules_ns if modules_ns else 1.0)


def _program(ev, mods, mod_starts, by_module):
    i = bisect.bisect_right(mod_starts, ev.start_ns) - 1
    if i < 0 or mods[i].end_ns < ev.end_ns:
        return None
    return by_module.get(re.sub(r"\(\d+\)$", "", mods[i].name))


def reduce_dir(path: str, hlo: dict) -> Reduction:
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return reduce(load(max(files, key=os.path.getmtime)), hlo)
