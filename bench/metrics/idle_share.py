"""Share of the traced window in which no operation ran on the device (%)."""


def read(rec):
    return 100.0 * (1.0 - rec.reduced.mean_busy_s() / rec.reduced.window_s)
