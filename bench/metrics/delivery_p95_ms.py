"""delivery_p95_ms: 95th percentile, over every message due in the
window, of due time to the host's receipt of the round in which its
last member delivered it."""

from benchlib import readers


def read(run):
    return readers.percentile(run, "latency_ms", 95)
