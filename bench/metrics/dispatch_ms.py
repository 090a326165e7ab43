"""Mean host duration of the program's ``engine.tick`` spans in the traced
window: the host's dispatch of one tick (ms)."""

from bench.program_trace import span_ms


def read(rec):
    return span_ms(rec, "engine.tick")
