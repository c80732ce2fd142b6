"""Seconds in the ckpt.capture spans (device -> host copy of the state)
inside each migrate, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "migrate", "ckpt.capture")
