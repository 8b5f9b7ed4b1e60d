"""delivery_rounds_p95.steady: 95th percentile, over every message due
in the window, of protocol rounds from the round it arrived in to the
round its last member delivered it (same round = 1)."""

from benchlib import readers


def read(run):
    return readers.percentile(run, "delivery_rounds", 95)
