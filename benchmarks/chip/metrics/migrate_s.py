"""Seconds in sess.migrate(MigrateRequest): quiesce, drain, capture, tree
digest, dump and commit; mean per cycle. The host clock starts at the step
boundary, so the in-flight step is not in it."""
import bench


def read(rec):
    return bench.mean_of(rec, "migrate")
