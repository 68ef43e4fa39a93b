"""The program's own spans and scope names in a JAX profiler trace.

With MPISPPY_TPU_TELEMETRY enabled, each span of the program's tracer
(mpisppy_tpu/telemetry/tracer.py) is also a profiler annotation
"sppy.<name>" on the host plane, on the clock of the benchmark's
"bench.<name>" spans and of the device ops.  The device ops of the
batched matvecs and of the consensus reduction carry the scope names
`pdhg_matvec` and `pdhg_consensus` (mpisppy_tpu/ir.py) in their `tf_op`
path.  That path is a stat of each device plane's event metadata, which
`jax.profiler.ProfileData` does not return, so it is read here from the
xplane's protobuf wire format (no proto package is needed).

A `Program` sits beside benchmark/trace.py's `Trace`, which it leaves
as it is: the readers below take both, and the trace's window.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from benchmark import trace as T

PREFIX = "sppy."
SOLVE = "sppy.ef.solve"
MATVEC = "pdhg_matvec"
CONSENSUS = "pdhg_consensus"
# ops that only hold other ops: their interval is their body's time
CONTAINERS = ("while", "conditional", "call")
_TPU = re.compile(r"^/device:TPU:(\d+)$")

# field numbers of the xplane messages read here (tsl/profiler/protobuf/
# xplane.proto): XSpace.planes; XPlane.name, .event_metadata and
# .stat_metadata (maps: entry key 1, value 2); XEventMetadata.name and
# .stats; XStatMetadata.name; XStat.metadata_id, .str_value, .ref_value
_SPACE_PLANE = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_MAP_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7


@dataclasses.dataclass
class Program:
    """spans = [(start_ns, end_ns, "sppy.<name>")] of the program's host
    annotations; launches = [start_ns] of the host's program launches;
    scope[chip] = {op name (as trace.op_name gives it): scope path}."""
    spans: list
    launches: list
    scope: dict


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, None for a fixed
    32- or 64-bit one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == _MAP_VALUE), b"")


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def scope_map(xspace):
    """{chip: {op name: tf_op scope path}} of each TPU device plane of a
    serialized XSpace; the path without its ":<type>" suffix."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != _SPACE_PLANE:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == _PLANE_NAME:
                name = _text(v)
            elif g == _PLANE_EVENT_META:
                events.append(_map_value(v))
            elif g == _PLANE_STAT_META:
                sm = dict(_fields(_map_value(v)))
                stat_names[sm.get(1, 0)] = _text(sm.get(_META_NAME, b""))
        m = _TPU.match(name)
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        if not m or not tf_op:
            continue
        ops = {}
        for ev in events:
            op, path = None, None
            for g, v in _fields(ev):
                if g == _META_NAME:
                    op = _text(v)
                elif g == _META_STATS:
                    st = dict(_fields(v))
                    if st.get(_STAT_ID) != tf_op[0]:
                        continue
                    path = (_text(st[_STAT_STR]) if _STAT_STR in st
                            else stat_names.get(st.get(_STAT_REF)))
            if op and path:
                ops[T.op_name(op)] = path.rsplit(":", 1)[0]
        out[int(m.group(1))] = ops
    return out


def from_profile(profile, xspace):
    """Program from a jax ProfileData and the XSpace bytes it was read
    from."""
    spans, launches = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
                elif e.name == T.LAUNCH:
                    launches.append(e.start_ns)
    return Program(spans=sorted(spans), launches=sorted(launches),
                   scope=scope_map(xspace))


def load(trace_dir):
    from jax.profiler import ProfileData
    with open(T.newest_xplane(trace_dir), "rb") as f:
        xspace = f.read()
    return from_profile(ProfileData.from_serialized_xspace(xspace), xspace)


def solve_spans(prog, lo, hi, name=SOLVE):
    """[(start, end)] of the program spans called `name` inside [lo, hi]."""
    return [(s, e) for s, e, n in prog.spans
            if n == name and lo <= s and e <= hi]


def leaf(ops):
    """A chip's ops (sorted by start) without the containers (while,
    conditional, call) whose interval encloses another op."""
    starts = [s for s, _, _ in ops]
    out = []
    for i, (s, e, n) in enumerate(ops):
        if n.rsplit(" ", 1)[-1] in CONTAINERS and any(
                k != i and ops[k][1] <= e
                for k in range(bisect.bisect_left(starts, s),
                               bisect.bisect_left(starts, e))):
            continue
        out.append((s, e, n))
    return out


def scope_of(path):
    """The innermost of the two scope names in a tf_op path, or None."""
    for part in reversed(path.split("/")):
        if part in (MATVEC, CONSENSUS):
            return part
    return None


def ef_idle_us(tr, prog, lo, hi):
    """Device-idle microseconds per EF solve while its `sppy.ef.solve`
    span is open, mean over chips; None without such spans."""
    spans = solve_spans(prog, lo, hi)
    if not spans or not tr.chips:
        return None
    within = T.union(spans)
    idle = sum(T.length(T.intersect(T.idle_gaps(tr, c, lo, hi), within))
               for c in tr.chips) / len(tr.chips)
    return idle * 1e-3 / len(spans)


def launches_per_solve(prog, lo, hi):
    """Host program launches inside the `sppy.ef.solve` spans, per solve."""
    spans = solve_spans(prog, lo, hi)
    if not spans:
        return None
    n = sum(1 for t in prog.launches
            for s, e in spans if s <= t < e)
    return n / len(spans)


def scope_shares(tr, prog, lo, hi):
    """{MATVEC, CONSENSUS, None: % of the leaf device time inside the
    `sppy.ef.solve` spans}, None the ops under neither scope; None when
    the trace has no such spans or no scope paths (a CPU trace)."""
    spans = solve_spans(prog, lo, hi)
    if not spans or not any(prog.scope.get(c) for c in tr.chips):
        return None
    within = T.union(spans)
    ns = {MATVEC: 0.0, CONSENSUS: 0.0, None: 0.0}
    for chip in tr.chips:
        scope = prog.scope.get(chip, {})
        for s, e, n in leaf(tr.ops[chip]):
            t = T.length(T.intersect([(s, e)], within))
            if t > 0:
                ns[scope_of(scope.get(n, ""))] += t
    total = sum(ns.values())
    if total <= 0:
        return None
    return {k: 100.0 * v / total for k, v in ns.items()}


def idle_by_program_span(tr, prog, lo, hi):
    """trace.idle_by_span over the benchmark's spans and the program's
    together: each idle gap named by the innermost span open during it,
    a "sppy.<name>" one wherever the program had one open, so a parent
    span's entry is its idle time that no child span covers."""
    both = T.Trace(ops=tr.ops, spans=sorted(tr.spans + prog.spans))
    return T.idle_by_span(both, lo, hi)
