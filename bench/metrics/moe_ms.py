"""Device self time per tick of the ops under the program's ``moe`` scope:
router, dispatch, the held experts, combine and shared experts, forward,
recomputed and backward (ms)."""

from bench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "moe")
