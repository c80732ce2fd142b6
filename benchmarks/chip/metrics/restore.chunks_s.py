"""Seconds in ckpt.restore.chunks (chunk read, verify and decode over the
pools) inside each restore, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "restore", "ckpt.restore.chunks")
