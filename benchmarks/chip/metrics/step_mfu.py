"""Model FLOPs of the window's steps over chips x bf16 peak x the steps'
wall time, in percent (flops.py counts them from the shapes)."""
import flops
import peaks


def read(rec):
    t0, t1 = rec["window"]
    steps = [s for s in rec["spans"] if s["name"] == "step"
             and t0 <= s["t0"] <= t1]
    if not steps:
        return None
    job = rec["job"]
    work = flops.train_flops_per_token(job.cfg, job.seq) \
        * len(steps) * job.gb * job.seq
    peak = peaks.peak(rec["device"]["kind"])["bf16_flops_per_s"]
    wall = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * work / (rec["chips"] * peak * wall)
