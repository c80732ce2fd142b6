"""Preempt -> durable -> resume cycles, the paper's scenario.

Each cycle trains ``steps_per_cycle`` steps. A timer thread sends SIGTERM
to the process at an instant drawn from the seed inside the last of them;
the session's PreemptionHandler flags it and the loop, polling at step
boundaries as launch/train.py does, turns it into a MigrateRequest. The
next incarnation opens its own session, restores the newest image, places
it on the device (``place`` span) and trains its first step. Cycles repeat
until the window has closed; the cycle running at the close finishes and
counts.

The answers checked are the images read back: each restored state against
the fingerprint of the state that was dumped, and every batch fed against
the reference stream (the data cursor the image carries).
"""
from __future__ import annotations

import gc
import os
import signal
import threading
import time

from bench import batch_digest


def run(job, seconds: float, rec) -> dict:
    import jax
    from repro.api import MigrateRequest, RestoreRequest

    t = job.traffic
    k = t["steps_per_cycle"]
    lo, hi = t["signal_at"]       # share of the step at which SIGTERM lands
    step_s = job.warm()
    job.open_session()
    start = job.fingerprint()
    cycles = []
    t_w0 = time.perf_counter()
    rec.add("setup_end", t_w0, t_w0)
    with rec.span("window"):
        while True:
            sig = {}

            def fire(sig=sig):
                sig["t"] = time.perf_counter()
                os.kill(os.getpid(), signal.SIGTERM)

            with rec.span("cycle"):
                j = 0
                while not job.sess.should_migrate():
                    j += 1
                    timer = None
                    if j == k:
                        timer = threading.Timer(
                            float(job.rng.uniform(lo, hi)) * step_s, fire)
                        timer.start()
                    with rec.span("step"):
                        job.train_step()
                    if timer is not None:
                        timer.join()
                    if j > 10 * k:
                        raise RuntimeError("SIGTERM never reached the loop")
                with rec.span("migrate"):
                    job.sess.migrate(MigrateRequest(
                        state=job.state, iterator=job.it, opt_cfg=job.opt))
                rec.add("preempt_durable", sig["t"], time.perf_counter())
                with rec.span("reference"):
                    want = job.fingerprint()
                job.close_session()
                job.state = None
                gc.collect()

                t_r0 = time.perf_counter()
                sess = job.open_session()
                with rec.span("restore"):
                    res = sess.restore(RestoreRequest(
                        target_struct=job.abstract))
                with rec.span("place"):
                    state = jax.block_until_ready(
                        jax.device_put(res.state, job.shardings))
                job.it = res.make_iterator(job.ds)
                del res
                with rec.span("reference") as ref:
                    got = job.fingerprint(state)
                job.state = state
                del state
                job.it.start_prefetch()
                first = job.gstep
                with rec.span("step"):
                    job.train_step()
                t_r1 = time.perf_counter()
                rec.add("resume_step", t_r0, t_r1,
                        excluded=ref["t1"] - ref["t0"])
                cycles.append({"want": want, "got": got, "first": first})
            if time.perf_counter() - t_w0 >= seconds:
                break
    return {"events": cycles, "start": start}


def check(job, out) -> dict:
    """Every cycle's restored state against the state it dumped, every
    batch fed against the reference stream, and that training moved every
    leaf before the first dump. Exact, so every limit is 0."""
    fed = dict(job.fed)
    leaves = failed = 0
    for c in out["events"]:
        bad = job.leaf_mismatches(c["want"], c["got"])
        ref = batch_digest(job.corpus.batch(c["first"], job.gb, job.seq))
        leaves += bad
        failed += bool(bad or fed[c["first"]] != ref)
    checks = {"leaf_mismatch": leaves, "batch_mismatch": job.batch_mismatches(),
              "leaves_unmoved": int((out["start"] == out["events"][0]["want"])
                                    .all(axis=1).sum())}
    return {"attempted": len(out["events"]), "failed": failed,
            "checks": {k: {"value": v, "limit": 0} for k, v in checks.items()}}
