"""Seconds in ckpt.dump.chunks (serialize, chunk hash, dedup probe, chunk
write and fsync over the pools) inside each migrate, mean per cycle."""
from program_spans import seconds_in


def read(rec):
    return seconds_in(rec, "migrate", "ckpt.dump.chunks")
