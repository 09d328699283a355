"""From a profiler trace to the numbers the per-layer metrics read.

``read(path, op_names)`` loads an ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps two things: every operation that
ran on each chip's ``XLA Ops`` line, and the benchmark's own host spans
(``bench.*`` annotations around the calls of each chunk).  The traced
window runs from the start of the first ``bench.chunk`` span to the end
of the last; everything is clipped to it.

On a TPU an operation's event is named by its HLO instruction and
carries no name stack, and a ``while`` loop's event spans every
operation of its body.  So each operation gets its self time (its
duration less that of the operations nested in it), and the name stack
(``op_name`` metadata, with the ``jit(...)`` scope of every jitted
wrapper it came through) is read from the optimized HLO text of the round
program (``op_names_from_hlo``), for the operations that ran inside that
program's module events.

A layer's device time is the self time of the operations whose name
stack passes through one of the layer's scopes (``jit(fed_direction_flat)``
for the local update).  XLA may fuse an operation of a layer with one
outside it; a fusion is attributed by its own ``op_name``, and
``mixed_s`` measures the time of fusions whose body holds operations both
inside and outside a layer.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ROUND_MODULE = "jit__run_rounds_impl"
CHUNK_SPAN = "bench.chunk"
SPAN_PREFIX = "bench."
INSTR = re.compile(r"^%?([\w.\-]+)")
# collective instructions, by opcode; names spell it with '-' or '_'
COLLECTIVE = re.compile(r"^(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|reduce[-_]scatter"
                        r"|collective[-_]permute)")


@dataclasses.dataclass(frozen=True)
class Op:
    start: int  # ns
    end: int
    self_ns: int  # duration less the operations nested in it
    name: str  # HLO instruction name
    stacks: Tuple[str, ...]  # op_name of the instruction, then of its fused body


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]  # chip id -> operations, sorted by start
    spans: List[Tuple[int, int, str]]  # benchmark host spans (start, end, name)
    window: Tuple[int, int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


# ---------------------------------------------------------------- HLO text

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def op_names_from_hlo(text: str) -> Dict[str, List[str]]:
    """instruction name -> [its op_name, then the op_names of the body it
    calls (a fusion's fused computation)], from optimized HLO text."""
    own, calls, body = {}, {}, defaultdict(list)
    comp = None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            comp = h.group(1)
            continue
        m = _LINE.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        own[m.group(1)] = op.group(1) if op else ""
        if op and comp:
            body[comp].append(op.group(1))
        c = _CALLS.search(line)
        if c:
            calls[m.group(1)] = c.group(1)
    return {k: [v] + body.get(calls.get(k, ""), []) for k, v in own.items()}


# ---------------------------------------------------------------- trace


def _self_times(events):
    """events: (start, end, name) of one line, which nest.  Returns the
    same with each event's self time appended."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    child = [0] * len(events)
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(s, e, n, max(0, (e - s) - c)) for (s, e, n), c in zip(events, child)]


def read(path, op_names: Optional[Dict[str, List[str]]] = None) -> Trace:
    from jax.profiler import ProfileData

    op_names = op_names or {}
    data = ProfileData.from_file(str(path))
    raw: Dict[int, list] = defaultdict(list)
    modules: Dict[int, list] = defaultdict(list)
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = raw if line.name == OPS_LINE else modules
                for e in line.events:
                    s = int(e.start_ns)
                    dest[int(m.group(1))].append((s, s + int(e.duration_ns), e.name))
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((s, s + int(e.duration_ns), e.name))
    chunks = [s for s in spans if s[2] == CHUNK_SPAN]
    if not chunks:
        raise ValueError(f"{path}: no {CHUNK_SPAN!r} span")
    window = (min(s[0] for s in chunks), max(s[1] for s in chunks))
    ops = {}
    for chip, events in raw.items():
        rounds = sorted((s, e) for s, e, n in modules[chip] if n.startswith(ROUND_MODULE))
        keep = []
        for s, e, n, self_ns in _self_times(events):
            if e <= window[0] or s >= window[1]:
                continue
            instr = INSTR.match(n).group(1)
            inside = any(a <= s and e <= b for a, b in rounds)
            stacks = tuple(op_names.get(instr, ())) if inside else ()
            lo, hi = max(s, window[0]), min(e, window[1])
            frac = (hi - lo) / (e - s) if e > s else 1.0
            keep.append(Op(lo, hi, int(self_ns * frac), instr, stacks))
        ops[chip] = sorted(keep, key=lambda o: o.start)
    spans = sorted(s for s in spans if s[1] > window[0] and s[0] < window[1])
    return Trace(ops, spans, window)


# ---------------------------------------------------------------- reductions


def union(intervals) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.ops:
        return 0.0
    return sum(sum(e - s for s, e in union((o.start, o.end) for o in lst))
               for lst in trace.ops.values()) * 1e-9 / len(trace.ops)


def _in_scope(stack: str, scopes) -> bool:
    return any(f"jit({s})" in stack for s in scopes)


def layer_s(trace: Trace, scopes) -> Optional[float]:
    """Self seconds of the operations whose own name stack passes through
    one of ``scopes``, averaged over the chips; None where none ran."""
    total, seen = 0, False
    for lst in trace.ops.values():
        for o in lst:
            if o.stacks and _in_scope(o.stacks[0], scopes):
                total += o.self_ns
                seen = True
    return total * 1e-9 / len(trace.ops) if seen else None


def mixed_s(trace: Trace, scopes) -> float:
    """Self seconds of fusions whose body holds operations both inside
    and outside ``scopes``, averaged over the chips."""
    total = 0
    for lst in trace.ops.values():
        for o in lst:
            body = [s for s in o.stacks[1:] if s]
            if body and len({_in_scope(s, scopes) for s in body}) == 2:
                total += o.self_ns
    return total * 1e-9 / max(1, len(trace.ops))


def self_s(trace: Trace, match: Callable[[Op], bool]) -> Optional[float]:
    """Self seconds of the matching operations, averaged over the chips;
    None where none ran.  An operation on a chip's ``XLA Ops`` line holds
    the chip's core while it runs, so for a collective this is the time
    the chip waited on the exchange with nothing else to do."""
    total, seen = 0, False
    for lst in trace.ops.values():
        for o in lst:
            if match(o):
                total += o.self_ns
                seen = True
    return total * 1e-9 / len(trace.ops) if seen else None


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.name))


def _label(op: Op) -> str:
    """Instruction name and the tail of its name stack."""
    stack = op.stacks[0] if op.stacks else ""
    scopes = re.findall(r"jit\(([^)]*)\)", stack)
    tail = stack.rsplit("/", 1)[-1] if stack else ""
    inner = scopes[-1] if len(scopes) > 1 else ""
    return " ".join(x for x in (op.name, "/".join(y for y in (inner, tail) if y)) if x)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The operations that took most device self time (seconds per chip)
    and the longest idle gaps, each named by the benchmark span the host
    was in when the gap began."""
    per = defaultdict(int)
    for lst in trace.ops.values():
        for o in lst:
            per[_label(o)] += o.self_ns
    n = max(1, len(trace.ops))
    device_ops = sorted(([k, v * 1e-9 / n] for k, v in per.items()), key=lambda kv: -kv[1])[:top]
    gaps = []
    for lst in trace.ops.values():
        busy = union((o.start, o.end) for o in lst)
        edges = [trace.window[0]] + [t for iv in busy for t in iv] + [trace.window[1]]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    gaps.sort(reverse=True)
    idle = [[_span_at(trace, s), length * 1e-9] for length, s in gaps[:top]]
    return {"device_ops": device_ops, "idle_gaps": idle}


def _span_at(trace: Trace, t: int) -> str:
    """The innermost benchmark span open at ``t`` (outside any: 'host')."""
    best = None
    for s, e, name in trace.spans:
        if s <= t < e and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2] if best else "host"
