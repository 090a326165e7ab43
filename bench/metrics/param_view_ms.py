"""Device self time per tick of the ops under the program's ``param_view``
scope and its transpose: the leaf-wise views of the flat params and the
gradient pack (ms)."""

from bench.program_trace import part_ms


def read(rec):
    return part_ms(rec, "param_view")
