"""Device time by the layers the program names in its compiled step.

The program wraps each layer in a ``jax.named_scope``; the name lands in
the ``op_name`` of every HLO instruction the layer lowers to, forward
(``jit(step)/jvp(mgg.aggregate)/…``) and backward
(``jit(step)/transpose(jvp(mgg.aggregate))/…``).  Here:

* ``program_hlo``: the HLO text of each program in a profiler trace (the
  profiler keeps every program's HloProto in the trace's metadata plane);
* ``hlo_scopes``: each instruction's scope, from its metadata (a fusion's
  from the matmuls it runs, where it runs any);
* ``reduce_scopes``: the window's device seconds by scope;
* ``read``: all three, once per traced run, for the readers in
  ``chipbench/metrics/``.

The scope names are strings here, not imported from the program: a program
that drops a scope makes its metric read nothing, and cannot redefine it.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import tracereduce
from chipbench.tracereduce import _CALLS, _COMP, _INSTR, _OP_NAME, _OPCODE

AGGREGATE = "mgg.aggregate"
RING = "mgg.ring"          # nested inside mgg.aggregate, so counted there
UPDATE = "gnn.update"
LOSS = "gnn.loss"
OPTIMIZER = "train.optimizer"
SCOPES = (AGGREGATE, RING, UPDATE, LOSS, OPTIMIZER)
UNSCOPED = "unscoped"
MIXED = "mixed"            # a fusion of instructions from several scopes
MATMULS = ("dot", "convolution")

# a scope is one component of the op_name path, bare or inside the
# wrappers that transformations add: jvp(…), transpose(…), vmap(…)
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, SCOPES))
                    + r")(?=$|[/)])")


def scope_of(op_name: str) -> Optional[str]:
    """The outermost known scope in an ``op_name``, or None."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else None


def computations(hlo_text: str) -> Dict[str, List[Tuple[str, str, str]]]:
    """Computation name -> its instructions, as (name, opcode, the rest of
    the line after ``=``)."""
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
    return comps


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction that has one.

    A fusion that runs matmuls (``dot`` or ``convolution``, in its fused
    computation or one nested in it) takes their scope, and ``mixed`` where
    they differ: the matmul is the fusion's work, whatever scope the ops
    fused around it carry.  Any other instruction takes the outermost known
    scope in its own ``op_name``.  A fusion whose ``op_name`` names none
    takes the one scope that the instructions of its fused computation
    share, and ``mixed`` where they differ.  Instructions with no scope are
    left out."""
    comps = computations(hlo_text)
    memo: Dict[Tuple[str, bool], frozenset] = {}

    def inside(comp: str, matmuls: bool) -> frozenset:
        """Scopes of the instructions of ``comp`` (of its matmuls only,
        where ``matmuls``), through nested fusions."""
        key = (comp, matmuls)
        if key not in memo:
            memo[key] = frozenset()
            found = set()
            for _, op, rest in comps.get(comp, ()):
                if op == "fusion" and matmuls:
                    found |= fused(rest, True)
                elif not matmuls or op in MATMULS:
                    s = own(op, rest)
                    if s is not None:
                        found.add(s)
            memo[key] = frozenset(found)
        return memo[key]

    def fused(rest: str, matmuls: bool) -> frozenset:
        return frozenset().union(*(inside(c, matmuls)
                                   for c in _CALLS.findall(rest)))

    def one(found: frozenset) -> Optional[str]:
        if len(found) == 1:
            (scope,) = found
            return scope
        return MIXED if found else None

    def own(op: str, rest: str) -> Optional[str]:
        if op == "fusion":
            scope = one(fused(rest, True))
            if scope is not None:
                return scope
        meta = _OP_NAME.search(rest)
        scope = scope_of(meta.group(1)) if meta else None
        if scope is None and op == "fusion":
            scope = one(fused(rest, False))
        return scope

    return {name: s for instrs in comps.values()
            for name, op, rest in instrs
            for s in [own(op, rest)] if s is not None}


_ENTRY = re.compile(r"^ENTRY\s+%?([^\s(]+)", re.MULTILINE)
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[\d,]*\]")


def matmul_ops(hlo_text: str) -> Dict[str, List[str]]:
    """Each instruction of the entry computation that runs matmuls (itself
    or inside its fusion) -> the result shapes of those matmuls, as
    ``f32[20000,16]``."""
    comps = computations(hlo_text)

    def shapes(instrs) -> List[str]:
        out = []
        for _, op, rest in instrs:
            if op in MATMULS:
                out.append(_SHAPE.match(rest).group(0))
            elif op == "fusion":
                for c in _CALLS.findall(rest):
                    out += shapes(comps.get(c, ()))
        return out

    entry = comps[_ENTRY.search(hlo_text).group(1)]
    return {name: found for name, op, rest in entry
            for found in [shapes([(name, op, rest)])] if found}


def reduce_scopes(trace: Dict, scopes: Dict[str, str]) -> Dict[str, float]:
    """Device seconds of each scope in the traced window, averaged over the
    devices, with ``unscoped`` for ops that have none; largest first.

    Where ops overlap on a device (an asynchronous copy beside a fusion),
    the overlap counts once, for the op that started first, so the values
    sum to the window's busy time."""
    lo, hi = tracereduce.window(trace)
    devs = sorted(trace["device"])
    if not devs:
        raise ValueError("the trace holds no device plane")
    total: Dict[str, int] = {}
    for dev in devs:
        covered = lo
        for name, s, e in sorted(tracereduce.clip(trace["device"][dev],
                                                  lo, hi),
                                 key=lambda ev: ev[1]):
            if e > covered:
                key = scopes.get(tracereduce.op_name(name), UNSCOPED)
                total[key] = total.get(key, 0) + e - max(s, covered)
                covered = e
    n = len(devs)
    return {k: v / n * 1e-9
            for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


# ---------------------------------------------------------------------------
# the programs' HLO, from the trace's metadata plane
# ---------------------------------------------------------------------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map: key 1,
# value 2); XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6;
# HloProto.hlo_module = 1 (tsl/profiler/protobuf/xplane.proto,
# xla/service/hlo.proto).

METADATA_PLANE = "/host:metadata"
_INSTR_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", re.MULTILINE)


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message; a
    length-delimited value is a memoryview into ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def program_hlo(xplane_path: str) -> Dict[str, str]:
    """HLO text (with metadata) of each program whose HloProto the trace
    keeps, keyed by the metadata's name (``jit_step(12)``)."""
    from jax._src.lib import _jax   # HloModule: parses and prints HLO

    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1 or bytes(_field(plane, 2) or b"") != \
                METADATA_PLANE.encode():
            continue
        for f, entry in _fields(plane):
            meta = _field(entry, 2) if f == 4 else None
            if meta is None:
                continue
            name = bytes(_field(meta, 2) or b"").decode()
            for g, stat in _fields(meta):
                proto = _field(stat, 6) if g == 5 else None
                module = _field(proto, 1) if proto is not None else None
                if module is not None:
                    out[name] = _jax.HloModule \
                        .from_serialized_hlo_module_proto(bytes(module)) \
                        .to_string()
    return out


def step_program(trace: Dict, programs: Dict[str, str]) -> Optional[str]:
    """The program that ran in the window: the one whose instruction names
    cover the most of the window's device time."""
    lo, hi = tracereduce.window(trace)
    time_of: Dict[str, int] = {}
    for evs in trace["device"].values():
        for name, s, e in tracereduce.clip(evs, lo, hi):
            op = tracereduce.op_name(name)
            time_of[op] = time_of.get(op, 0) + e - s
    best, best_ns = None, 0
    for key, text in programs.items():
        names = set(_INSTR_NAME.findall(text))
        ns = sum(t for op, t in time_of.items() if op in names)
        if ns > best_ns:
            best, best_ns = key, ns
    return best


def trace_scopes(xplane_path: str) -> Dict[str, float]:
    """The window's device seconds by scope, from one traced run's
    ``.xplane.pb``: its device ops, window and programs' HLO."""
    trace = tracereduce.events_from_xspace(xplane_path)
    programs = program_hlo(xplane_path)
    step = step_program(trace, programs)
    return reduce_scopes(trace, hlo_scopes(programs[step]) if step else {})


def read(ctx) -> Dict[str, float]:
    """The scope seconds of this run's traced window, computed once per run
    and kept in ``ctx``; they also join the result line's ``breakdown`` as
    ``scopes``.  Empty where the program names no scope."""
    if "scopes" not in ctx:
        from chipbench import harness
        secs = trace_scopes(harness._xplane(harness.TRACE_DIR))
        if set(secs) <= {UNSCOPED}:
            secs = {}
        ctx["scopes"] = secs
        if secs:
            ctx["trace"]["breakdown"]["scopes"] = [[k, v]
                                                   for k, v in secs.items()]
    return ctx["scopes"]
