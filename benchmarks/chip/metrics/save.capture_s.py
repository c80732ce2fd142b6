"""Seconds in the ckpt.capture spans (device -> host copy of the state)
inside each async save call, mean per save."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "save_call", "ckpt.capture")
