"""Device time per tick of every op that is neither an update kernel nor a
collective: the step body, forward and backward (ms)."""


def read(rec):
    if rec.ticks == 0:
        return None
    return 1e3 * rec.reduced.class_s("other") / rec.ticks
