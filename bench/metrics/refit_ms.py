"""Mean host duration of the program's ``refresh.refit`` spans in the traced
window: the host refit of alpha(tau) at a refresh (ms)."""

from bench.program_trace import span_ms


def read(rec):
    return span_ms(rec, "refresh.refit")
