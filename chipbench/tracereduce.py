"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is read into plain lists first (``events_from_xspace``):

* ``device``: per device, ``[name, start_ns, dur_ns]`` of the operations
  the device ran (the "XLA Ops" line of each TPU plane);
* ``host``: ``[name, start_ns, dur_ns]`` of the benchmark's own host spans
  (``chipbench.*`` TraceAnnotations).

Everything after that works on those lists, so a trimmed trace kept as JSON
checks it in the tests.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_LINE = "XLA Ops"
TOP = 10


def events_from_xspace(path: str) -> Dict:
    """Device op events and the benchmark's host spans of an .xplane.pb."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    evs.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(SPAN_PREFIX))
    return dict(device=device, host=host)


def window(trace: Dict) -> Tuple[int, int]:
    """[start, end) of the traced window, from the window span."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def clip(events: Iterable[Sequence], lo: int, hi: int) -> List[Tuple]:
    """(name, start, end) of each event, clipped to [lo, hi)."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals: Iterable[Tuple]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals of (name, start, end) events."""
    merged: List[List[int]] = []
    for _, s, e in sorted(intervals, key=lambda t: t[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Tuple]) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals: Iterable[Tuple], lo: int, hi: int):
    """Idle [start, end) stretches of [lo, hi) between merged intervals."""
    out, t = [], lo
    for s, e in union(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Tuple[int, int], host: Iterable[Sequence]) -> str:
    """The innermost benchmark span (other than the window) that covers
    most of the gap, or "untraced"."""
    best, best_cover, best_len = "untraced", 0, None
    for name, s, d in host:
        if name == WINDOW_SPAN:
            continue
        cover = min(s + d, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and d < best_len):
            best, best_cover, best_len = name[len(SPAN_PREFIX):], cover, d
    return best


def reduce(trace: Dict, agg_ops) -> Dict:
    """Busy, idle and aggregation seconds of the window, averaged over the
    devices, and the breakdown (top device ops, longest labelled gaps)."""
    lo, hi = window(trace)
    devs = sorted(trace["device"])
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = agg = 0.0
    per_op: Dict[str, float] = {}
    all_gaps = []
    for dev in devs:
        evs = clip(trace["device"][dev], lo, hi)
        busy += busy_ns(evs)
        agg += busy_ns(ev for ev in evs if is_agg(ev[0], agg_ops))
        for name, s, e in evs:
            name = short_name(name)
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        all_gaps += gaps(evs, lo, hi)
    n = len(devs)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return dict(
        devices=n,
        window_s=(hi - lo) * 1e-9,
        busy_s=busy / n * 1e-9,
        agg_s=agg / n * 1e-9,
        breakdown=dict(
            device_ops=[[k, v / n * 1e-9] for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:TOP]],
            idle_gaps=[[label_gap(g, trace["host"]), (g[1] - g[0]) * 1e-9]
                       for g in all_gaps[:TOP]]))


# ---------------------------------------------------------------------------
# which device ops are aggregation: from the compiled program's HLO
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOWERED_FROM = re.compile(r"^(gather|scatter[a-z\-]*)$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?%?([^\s,}]+)")
AGG_OPCODES = ("gather", "scatter")  # and what XLA lowers them to
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def hlo_agg_ops(hlo_text: str) -> frozenset:
    """Names of the instructions that aggregate: every op whose HLO holds a
    gather or scatter (directly or in the computation it calls), every op
    lowered from one (its metadata ``op_name`` ends in ``gather`` or
    ``scatter*``, as the index sort of a scatter-add does), and every
    Pallas kernel (a ``tpu_custom_call``)."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith((" ", "\t")):
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            rest = m.group(2)
            op = _OPCODE.search(rest)
            cur.append((m.group(1), op.group(1) if op else "", rest))

    memo: Dict[str, bool] = {}

    def holds(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(own(op, rest) for _, op, rest
                             in comps.get(comp, ()))
        return memo[comp]

    def own(op: str, rest: str) -> bool:
        if op in AGG_OPCODES or KERNEL_TARGET in rest:
            return True
        meta = _OP_NAME.search(rest)
        if meta and _LOWERED_FROM.match(meta.group(1).rsplit("/", 1)[-1]):
            return True
        return op == "fusion" and any(holds(c) for c in _CALLS.findall(rest))

    return frozenset(name for instrs in comps.values()
                     for name, op, rest in instrs
                     if op not in ("while", "call", "conditional")
                     and own(op, rest))


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event, which the trace names by
    the instruction's whole text (``%fusion.8 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def short_name(event_name: str) -> str:
    """Instruction name, result shape and opcode, for the breakdown."""
    m = re.match(r"^%?(\S+) = (\(.*?\)|\S+) ([a-z][a-z0-9\-]*)\(",
                 event_name)
    return " ".join(m.groups()) if m else event_name[:120]


def is_agg(event_name: str, agg_ops) -> bool:
    return op_name(event_name) in agg_ops
