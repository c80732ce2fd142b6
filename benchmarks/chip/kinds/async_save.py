"""Training with background saves: a job that checkpoints while it trains.

Every ``save_every`` steps of the window the loop calls
``DumpRequest(mode="async")`` with the launch/train.py metadata; the
session captures the state and its async lane writes it while steps go on.
A step counts as overlapped when it starts while the newest image is not
yet committed (its manifest not yet in the store). The window closes at the
first step boundary after ``seconds``; then the lane is drained.

The answers checked, once the window has closed and the state is freed:
the newest image, restored and placed, against the fingerprint of the state
the save captured; the batch the image's data cursor feeds next, and every
batch fed in the window, against the reference stream; and that training
moved the state between the window's start and that save.
"""
from __future__ import annotations

import gc
import time

from bench import batch_digest


def run(job, seconds: float, rec) -> dict:
    from repro.api import DumpRequest
    from repro.core import train_meta

    every = job.traffic["save_every"]
    job.warm()
    sess = job.open_session()
    start = job.fingerprint()
    saves = []
    pending = None
    t_w0 = time.perf_counter()
    rec.add("setup_end", t_w0, t_w0)
    with rec.span("window"):
        i = 0
        while True:
            if pending is not None and sess.tier.exists(
                    sess.tier.manifest_path(pending)):
                pending = None
            with rec.span("step", overlapped=pending is not None):
                job.train_step()
            i += 1
            if time.perf_counter() - t_w0 >= seconds:
                break
            if i % every == 0:
                with rec.span("reference"):
                    want = job.fingerprint()
                step = int(job.state["step"])
                meta = train_meta(arch=job.cfg.name, step=step,
                                  data_state=job.it.state(),
                                  opt_cfg=job.opt)
                with rec.span("save_call"):
                    sess.dump(DumpRequest(state=job.state, step=step,
                                          meta=meta, mode="async"))
                pending = f"step_{step:010d}"
                saves.append({"want": want, "step": step,
                              "gstep": job.gstep})
    with rec.span("drain"):
        receipts = sess.wait()
    return {"events": saves, "start": start,
            "dumps": [r.stats for r in receipts]}


def check(job, out) -> dict:
    """Once the window has closed: free the state, restore the newest image
    and compare. Exact comparisons, so every limit is 0."""
    import jax
    from repro.api import RestoreRequest
    saves = out["events"]
    if not saves:
        raise RuntimeError("no save fell inside the window")
    job.state = None
    gc.collect()
    last = saves[-1]
    res = job.sess.restore(RestoreRequest(
        target_struct=job.abstract, image_id=f"step_{last['step']:010d}"))
    state = jax.device_put(res.state, job.shardings)
    it = res.make_iterator(job.ds, prefetch=1)
    del res
    got = job.fingerprint(state)
    del state
    cursor = int(batch_digest(it.next()) != batch_digest(
        job.corpus.batch(last["gstep"], job.gb, job.seq)))
    checks = {
        "leaf_mismatch": job.leaf_mismatches(last["want"], got),
        "cursor_mismatch": cursor,
        "batch_mismatch": job.batch_mismatches(),
        "leaves_unmoved": int((out["start"] == last["want"]).all(axis=1).sum()),
    }
    return {"attempted": 1, "failed": int(any(checks.values())),
            "checks": {k: {"value": v, "limit": 0} for k, v in checks.items()}}
