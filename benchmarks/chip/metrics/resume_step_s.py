"""New session + RestoreRequest -> the first step's loss ready on the
device, mean over the window's cycles (the benchmark's own fingerprint of
the restored state is left out)."""
import bench


def read(rec):
    return bench.mean_of(rec, "resume_step")
