"""Device self time per tick of the ops under the program's ``forward``
scope, its transpose (the backward pass) excluded (ms)."""

from bench.program_trace import part_ms


def read(rec):
    return part_ms(rec, "forward")
