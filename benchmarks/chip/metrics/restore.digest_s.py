"""Seconds in ckpt.tree_digest of the restored leaves inside each
restore, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "restore", "ckpt.tree_digest")
