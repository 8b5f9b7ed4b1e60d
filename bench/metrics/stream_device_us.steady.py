"""stream_device_us.steady: device time per execution of the stream round
program in the traced window."""

from benchlib import readers


def read(run):
    return readers.stream_program_us(run)
