"""Seconds of host -> device placement of the restored state into its
shardings, ended by block_until_ready; mean per cycle."""
import bench


def read(rec):
    return bench.mean_of(rec, "place")
