"""round_ms.saturated: host wall time per GroupStream.step round over the
window (dispatch, readbacks, host bookkeeping)."""

from benchlib import readers


def read(run):
    return readers.round_ms(run)
