"""Device time of the ops under one of the program's named scopes.

The program names its layers with nested scopes (``mla``; ``moe`` holding
``router``, ``dispatch``, ``experts``, ``combine``, ``shared``).  An op's
scope path (``program_trace.load``) carries them wherever the layer ran:
inside the scanned and recomputed stack, in the forward pass and in its
transpose, each component possibly wrapped (``transpose(jvp(mla))``).  An op
is under a scope when the unwrapped components of its path (the first of a
fused op's ``;``-joined paths) hold the scope's names in order, loops and
recomputation (``while``, ``body``, ``checkpoint``, ...) between them.
"""

from __future__ import annotations

from bench import trace as tr
from bench.program_trace import Scoped, _unwrap


def under(path: str, names: tuple[str, ...]) -> bool:
    parts = iter(_unwrap(c)[0] for c in path.split(";", 1)[0].split("/"))
    return all(name in parts for name in names)


def scope_s(scoped: Scoped, names: tuple[str, ...]) -> float | None:
    """Device self seconds of the class "other" under ``names``, averaged
    over the devices (each instant goes to the innermost op, as in
    ``Scoped.other_by_scope``); None where no op carries the scope."""
    total, seen = 0.0, False
    for ops in scoped.scoped.values():
        other = [(p, a, b) for n, a, b, p in ops if tr.op_class(n) == "other"]
        for path, t in tr._self_times(other):
            if under(path, names):
                total += t * 1e-9 / len(scoped.scoped)
                seen = True
    return total if seen else None


def scope_ms(rec, *names: str) -> float | None:
    """:func:`scope_s` per tick of the traced window (ms)."""
    scoped = Scoped.of(rec.reduced)
    if scoped is None or rec.ticks == 0:
        return None
    seconds = scope_s(scoped, names)
    return None if seconds is None else 1e3 * seconds / rec.ticks
