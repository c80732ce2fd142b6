"""Model FLOPs of the window's steps over chips x bf16 peak x the steps'
wall time, in percent (flops.py, from the configuration's sizes)."""
import flops
import peaks


def read(rec):
    t0, t1 = rec["window"]
    steps = [s for s in rec["spans"] if s["name"] == "step"
             and t0 <= s["t0"] <= t1]
    if not steps:
        return None
    job = rec["job"]
    work = flops.train_flops_per_token(job.model, job.conf, job.seq) \
        * len(steps) * job.gb * job.seq
    peak = peaks.peak(rec["device"]["kind"])["bf16_flops_per_s"]
    wall = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * work / (rec["chips"] * peak * wall)
