"""The program's own marks in a profiler trace: its host spans and the
named scopes its device ops carry.

:func:`load` is :func:`trace.load` with two more keys in the plain form::

    {"program": [[name, start_ns, dur_ns], ...],   # the program's host spans
     "device_scopes": {"0": [path, ...], ...}}     # scope path of each op
                                                   # in "devices", in order

The program's host spans are the ``TraceAnnotation`` events it writes
(``run.input``, ``run.hooks``, ``engine.tick``, ``engine.trace``,
``engine.refresh``, ``refresh.drain``, ``refresh.refit``,
``refresh.swap``); none is named like the harness's own spans.  An op's
scope path is the ``op_name`` metadata XLA keeps through fusion, e.g.
``jit(counting)/transpose(jvp(forward))/dot_general``; a fused op may join
several paths with ``;``, and is classed by the first.

:class:`Scoped` adds to :class:`trace.Reduced` the reductions that read
them: the device time of the class "other" split by the step body's scopes
(``param_view``, ``forward``, backward, ``staleness``, ``update``, and the
unscoped rest), the host durations of one program span, and the idle gaps
named by the innermost program span covering them.
"""

from __future__ import annotations

import re
import warnings
from collections import defaultdict

from bench import trace as tr

PROGRAM_SPAN = re.compile(r"^(run|engine|refresh)\.[a-z_]+$")
# The stat of an op's event metadata that holds its HLO instruction's
# op_name metadata, as "<path>:" (the TPU profiler's name for it).
SCOPE_STAT = "tf_op"
PARTS = ("param_view", "forward", "backward", "staleness", "update", "unscoped")
_WRAPPED = re.compile(r"^([\w.]+)\((.*)\)$")


# -- the few XSpace fields read here, from the protobuf wire format ---------
# ``jax.profiler.ProfileData`` gives each event's own stats but not those of
# its metadata, where the TPU profiler keeps ``tf_op``.  Field numbers are
# those of tsl/profiler/protobuf/xplane.proto.

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field, value)`` of the message ``buf[lo:hi]``: an int for a varint,
    the ``(lo, hi)`` of the bytes for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map(buf, span):
    """The ``(key, value)`` of one map entry."""
    entry = dict(_fields(buf, *span))
    return entry.get(1, 0), entry.get(2)


def _device_scopes(path: str, want: set) -> dict:
    """Device id -> the scope path of each event of its op line, in order."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:  # XSpace.planes
            continue
        name, lines, events_meta, stats_meta = "", [], {}, {}
        for f, v in _fields(buf, *plane):
            if f == 2:  # XPlane.name
                name = _text(buf, v)
            elif f == 3:  # XPlane.lines
                lines.append(v)
            elif f == 4:  # XPlane.event_metadata
                key, value = _map(buf, v)
                events_meta[key] = value
            elif f == 5:  # XPlane.stat_metadata
                key, value = _map(buf, v)
                stats_meta[key] = _text(buf, dict(_fields(buf, *value))[2]) if value else ""
        m = tr._DEVICE_PLANE.match(name)
        if not m or m.group(2) not in want:
            continue
        scope_id = next((k for k, n in stats_meta.items() if n == SCOPE_STAT), None)
        paths = {}

        def scope_of(meta_id):
            if meta_id not in paths:
                paths[meta_id] = ""
                meta = events_meta.get(meta_id)
                for f, stat in _fields(buf, *meta) if meta else ():
                    if f == 5:  # XEventMetadata.stats
                        s = dict(_fields(buf, *stat))
                        if s.get(1) == scope_id and 5 in s:  # XStat.str_value
                            paths[meta_id] = _text(buf, s[5]).rstrip(":")
            return paths[meta_id]

        scopes = out.setdefault(m.group(2), [])
        for line in lines:
            fields = list(_fields(buf, *line))
            if any(f == 2 and _text(buf, v) == tr.OP_LINE for f, v in fields):  # XLine.name
                for f, event in fields:
                    if f == 4:  # XLine.events
                        scopes.append(scope_of(dict(_fields(buf, *event)).get(1, 0)))
    return out


def load(path: str, devices: list[int]) -> dict:
    """:func:`trace.load`'s plain form of the trace at ``path``, with the
    program's host spans and each device op's scope path."""
    import jax

    plain = tr.load(path, devices)
    want = {str(d) for d in devices}
    plain["program"] = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if PROGRAM_SPAN.match(e.name):
                            span = [e.name, float(e.start_ns), float(e.duration_ns)]
                            plain["program"].append(span)
    scopes = _device_scopes(path, want)
    plain["device_scopes"] = {d: scopes.get(d, []) for d in sorted(want)}
    for d, ops in plain["devices"].items():
        if len(plain["device_scopes"][d]) != len(ops):
            n = len(plain["device_scopes"][d])
            raise ValueError(f"device {d}: {n} scope paths for {len(ops)} ops")
    return plain


def _unwrap(component: str) -> tuple[str, list[str]]:
    """``transpose(jvp(forward))`` -> ``("forward", ["transpose", "jvp"])``."""
    wrappers = []
    m = _WRAPPED.match(component)
    while m:
        wrappers.append(m.group(1))
        component = m.group(2)
        m = _WRAPPED.match(component)
    return component, wrappers


def scope_part(path: str) -> str:
    """The part of the step body an op belongs to, by its scope path."""
    scopes = []
    for component in path.split(";", 1)[0].split("/"):
        name, wrappers = _unwrap(component)
        if not (wrappers and wrappers[-1] == "jit"):  # jit(f) names a function
            scopes.append((name, wrappers))
    names = [n for n, _ in scopes]
    if "param_view" in names:
        return "param_view"
    for name, wrappers in scopes:
        if name == "forward":
            return "backward" if "transpose" in wrappers else "forward"
    for part in ("staleness", "update"):
        if part in names:
            return part
    return "unscoped"


def _innermost(spans, a: float, b: float) -> str | None:
    """The span that is innermost over most of ``[a, b]`` (None where no
    span covers any of it); ``spans`` are ``(name, start, end)``."""
    over = [s for s in spans if s[1] < b and s[2] > a]
    points = sorted({a, b} | {t for _, s, e in over for t in (s, e) if a < t < b})
    cover = defaultdict(float)
    for p, q in zip(points, points[1:]):
        mid = 0.5 * (p + q)
        inside = [s for s in over if s[1] <= mid < s[2]]
        if inside:
            name = max(inside, key=lambda s: (s[1], -s[2]))[0]
            cover[name] += q - p
    return max(cover, key=cover.get) if cover else None


class Scoped(tr.Reduced):
    """:class:`trace.Reduced` of a plain form that :func:`load` made."""

    def __init__(self, plain: dict):
        super().__init__(plain)
        self.scoped = {}  # device -> (name, start, end, scope path), clipped
        for d, events in plain["devices"].items():
            rows = []
            for (name, s, dur), path in zip(events, plain["device_scopes"][d]):
                a, b = max(s, self.lo), min(s + dur, self.hi)
                if b > a:
                    rows.append((name, a, b, path))
            self.scoped[d] = rows

    @classmethod
    def of(cls, reduced) -> "Scoped | None":
        """The scoped reductions of ``reduced``'s plain form; None where the
        trace was loaded without the program's marks."""
        plain = reduced.plain
        if "device_scopes" not in plain or "program" not in plain:
            return None
        return cls(plain)

    def other_by_scope(self) -> dict:
        """Device seconds of the class "other", split by :func:`scope_part`
        and averaged over the devices.  Each instant an "other" op runs goes
        to the innermost such op (its self time within the class), so the
        parts add up to ``class_s("other")``."""
        by = dict.fromkeys(PARTS, 0.0)
        for ops in self.scoped.values():
            other = [(p, a, b) for n, a, b, p in ops if tr.op_class(n) == "other"]
            for path, t in tr._self_times(other):
                by[scope_part(path)] += t * 1e-9 / len(self.scoped)
        return by

    def has_scope(self, part: str) -> bool:
        """Whether any op in the window carries ``part``'s scope."""
        return any(scope_part(p) == part for ops in self.scoped.values() for *_, p in ops)

    def span_s(self, name: str) -> list[float]:
        """Host seconds of each program span ``name`` that lies in the window."""
        return [d * 1e-9 for n, s, d in self.plain["program"]
                if n == name and s >= self.lo and s + d <= self.hi]

    def gaps(self) -> list[tuple[float, float]]:
        """The idle gaps of the first device in the window, longest first."""
        device = sorted(self.ops)[0]
        busy = tr._union([(a, b) for _, a, b in self.ops[device]])
        gaps, cur = [], self.lo
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if self.hi > cur:
            gaps.append((cur, self.hi))
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def idle_gaps_program(self, k: int | None = 10) -> list:
        """The ``k`` longest idle gaps of the first device (all with None),
        each named by the program span innermost over most of it
        (``python`` where none covers it)."""
        spans = [(n, s, s + d) for n, s, d in self.plain["program"]]
        return [[_innermost(spans, a, b) or "python", (b - a) * 1e-9]
                for a, b in self.gaps()[:k]]


def part_ms(rec, part: str):
    """Device self time per tick of the "other" ops in scope ``part`` (ms);
    None where the trace lacks the scope."""
    scoped = Scoped.of(rec.reduced)
    if scoped is None or rec.ticks == 0 or not scoped.has_scope(part):
        return None
    return 1e3 * scoped.other_by_scope()[part] / rec.ticks


def span_ms(rec, name: str):
    """Mean host duration of the program's span ``name`` in the window (ms);
    None where it has none."""
    scoped = Scoped.of(rec.reduced)
    durations = scoped.span_s(name) if scoped is not None else []
    if not durations:
        return None
    return 1e3 * sum(durations) / len(durations)
