"""device_idle_share.steady: share of the traced window in which no
operation ran on the device, in percent."""

from benchlib import readers


def read(run):
    return readers.idle_percent(run)
