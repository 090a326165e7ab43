"""Device self time per tick of the ops under ``transpose(jvp(forward))``,
outside the program's ``param_view`` scope: the backward pass (ms)."""

from bench.program_trace import part_ms


def read(rec):
    return part_ms(rec, "backward")
