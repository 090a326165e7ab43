"""Host time per ``engine.refresh`` call in the traced window (ms), from the
benchmark's engine wrapper."""


def read(rec):
    if not rec.refresh_s:
        return None
    return 1e3 * sum(rec.refresh_s) / len(rec.refresh_s)
