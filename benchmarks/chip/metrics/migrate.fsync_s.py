"""Thread-seconds of the fsync counter (os.fsync of each chunk file and
the manifest, summed over the pool threads) on the ckpt.dump inside each
migrate, mean per cycle."""
from program_spans import counter_seconds_in


def read(rec):
    return counter_seconds_in(rec, "migrate", "ckpt.dump", "fsync")
