"""Seconds in ckpt.cast (each restored leaf cast to the target dtype)
inside each restore, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "restore", "ckpt.cast")
