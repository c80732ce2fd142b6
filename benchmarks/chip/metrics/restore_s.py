"""Seconds in sess.restore(RestoreRequest): fetch, verify, decode, digest.
Mean per cycle."""
import bench


def read(rec):
    return bench.mean_of(rec, "restore")
