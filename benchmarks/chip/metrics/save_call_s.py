"""Seconds the job waits in DumpRequest(mode="async"): capture and
enqueue; mean per save."""
import bench


def read(rec):
    return bench.mean_of(rec, "save_call")
