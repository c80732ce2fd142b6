"""Tokens trained in the window over the window's length, background saves
included (the benchmark's fingerprints of the saved states left out)."""
import bench


def read(rec):
    t0, t1 = rec["window"]
    steps = [s for s in rec["spans"] if s["name"] == "step"
             and t0 <= s["t0"] <= t1]
    if not steps:
        return None
    ref = sum(bench.durations({"spans": [
        s for s in rec["spans"] if s["name"] == "reference"
        and t0 <= s["t0"] <= t1]}, "reference"))
    job = rec["job"]
    return len(steps) * job.gb * job.seq / (t1 - t0 - ref)
