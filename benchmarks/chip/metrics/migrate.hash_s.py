"""Thread-seconds of the chunk_hash counter (SHA-256 of each chunk,
summed over the pool threads) on the ckpt.dump inside each migrate, mean
per cycle."""
from program_spans import counter_seconds_in


def read(rec):
    return counter_seconds_in(rec, "migrate", "ckpt.dump", "chunk_hash")
