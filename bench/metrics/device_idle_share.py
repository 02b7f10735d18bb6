"""Percent of the traced window in which no operation ran on the device."""


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 100.0 * rec.trace.idle_share
