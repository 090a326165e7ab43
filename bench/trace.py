"""From one profiler trace to the per-layer metrics' raw material.

:func:`load` reads the ``.xplane.pb`` the JAX profiler writes into a small
plain form, the same form the tests' recorded fixture holds::

    {"window_ns": [lo, hi],                       # the traced window
     "devices": {"0": [[name, start_ns, dur_ns], ...], ...},  # device ops
     "host": [[name, start_ns, dur_ns], ...]}      # the harness's spans

Device ops are the events of each device plane's op line; host spans are
the ``TraceAnnotation`` events the harness writes (``window``, ``tick``,
``refresh``, ``wait``).  Everything below works on that form: busy time as
the union of op intervals inside the window, op time by name and by class
(update kernel, collective, the rest), and the idle gaps with the host span
that covers most of each.
"""

from __future__ import annotations

import glob
import os
import re
import warnings
from collections import defaultdict

HOST_SPANS = ("window", "tick", "refresh", "wait")
OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")

# The optimizer update's Pallas launches, by the HLO instruction they
# appear as on the TPU: the one-launch async tick, the chain of the sync
# step and the combine of the clip variant (each a custom call named after
# the jitted function that launches it in kernels/adaptive_update/fused.py).
UPDATE_KERNEL = re.compile(r"^%?fused_(tick|chain|combine)_call\b")
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def short_name(text: str) -> str:
    """An op's instruction name: ``%fusion.12 = f32[...] fusion(...)`` on the
    TPU gives ``%fusion.12``; a name without ``=`` stays whole."""
    return text.split(" = ", 1)[0].strip()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, devices: list[int]) -> dict:
    """The plain form of the trace at ``path`` for the device ids given."""
    import jax

    want = {str(d) for d in devices}
    out = {"window_ns": None, "devices": {d: [] for d in sorted(want)}, "host": []}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m and m.group(2) in want:
                for line in plane.lines:
                    if line.name == OP_LINE:
                        out["devices"][m.group(2)] += [
                            [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                        ]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in HOST_SPANS:
                            out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    windows = [(s, s + d) for n, s, d in out["host"] if n == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    out["window_ns"] = [min(a for a, _ in windows), max(b for _, b in windows)]
    return out


def op_class(name: str) -> str:
    if UPDATE_KERNEL.search(name):
        return "update"
    if COLLECTIVE.search(name):
        return "collective"
    return "other"


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _self_times(ops):
    """``(name, self_ns)`` of each op: its interval less the intervals of
    the ops nested directly inside it on the same line."""
    out, stack = [], []  # stack entries: [name, end, self_ns]
    for n, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            name, _, t = stack.pop()
            out.append((name, t))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([n, b, b - a])
    out += [(name, t) for name, _, t in stack]
    return out


class Reduced:
    """The reductions of one trace's plain form."""

    def __init__(self, plain: dict):
        self.plain = plain
        self.lo, self.hi = plain["window_ns"]
        self.ops = {d: list(_clip(ev, self.lo, self.hi)) for d, ev in plain["devices"].items()}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, device: str) -> float:
        return sum(b - a for a, b in _union([(a, b) for _, a, b in self.ops[device]])) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def class_s(self, cls: str) -> float:
        """Device seconds in which an op of one class ran (the union of their
        intervals: a loop's op holds its body's), averaged over the devices."""
        total = 0.0
        for ops in self.ops.values():
            total += sum(b - a for a, b in _union([(a, b) for n, a, b in ops if op_class(n) == cls]))
        return total * 1e-9 / len(self.ops)

    def class_count(self, cls: str) -> float:
        n = sum(1 for ops in self.ops.values() for name, _, _ in ops if op_class(name) == cls)
        return n / len(self.ops)

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops that took most device time, by name, each op's time
        less that of the ops it holds (a loop's body), averaged over devices."""
        by = defaultdict(float)
        for ops in self.ops.values():
            for n, t in _self_times(ops):
                by[n] += t * 1e-9 / len(self.ops)
        return sorted(([n, s] for n, s in by.items()), key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest idle gaps of the first device, each named after the
        host span that covers most of it (``python`` where none does)."""
        device = sorted(self.ops)[0]
        busy = _union([(a, b) for _, a, b in self.ops[device]])
        gaps, cur = [], self.lo
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if self.hi > cur:
            gaps.append((cur, self.hi))
        spans = [(n, s, s + d) for n, s, d in self.plain["host"] if n != "window"]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            cover = defaultdict(float)
            for n, s, e in spans:
                cover[n] += max(0.0, min(b, e) - max(a, s))
            name = max(cover, key=cover.get) if cover and max(cover.values()) > 0 else "python"
            out.append([name, (b - a) * 1e-9])
        return out
