"""Device self time per tick of the ops under the program's ``mla`` scope,
latent attention forward, recomputed and backward (ms)."""

from bench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "mla")
