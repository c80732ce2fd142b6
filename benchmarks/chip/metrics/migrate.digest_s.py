"""Seconds in ckpt.tree_digest (SHA-256 over every leaf, one thread)
inside each migrate, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "migrate", "ckpt.tree_digest")
