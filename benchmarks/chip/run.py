#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration file and a
traffic file; the traffic's ``kind`` names the generator under ``kinds/``.
Set-up makes the job from the seed (corpus, state on the device, step
program from the compile cache) and trains its first steps, then the window
runs for ``--seconds``. Once it has closed and the state is freed, the
plain reference (the module under ``references/`` that the configuration
file names, with ``reference.py``) follows the first steps and the
program's readings of them are compared with it.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from spans and a profiler trace of
the window by the readers under ``metrics/``. The last line of standard
output is the JSON result; the numbers compared, each with its limit, come
last in it and last on standard error.

Needs the TPU devices the cell asks for: without them it exits 3 and prints
no result. Each run keeps its checkpoint store and data in a fresh
directory under the temp directory and deletes it on exit; JAX's compile
cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402
import reference  # noqa: E402


def metric_names(spec: dict, section: str, cell: str) -> list:
    return [m for m in spec[section]
            if cell in m.get("workloads", [cell])]


def is_correct(res: dict) -> bool:
    """No answer failed and every number compared is within its limit."""
    return res["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in res["checks"].values())


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             chips: int, t_start: float, codec: dict | None = None) -> dict:
    """Set-up, window, post-window checks and metrics of one run; returns
    the result fields. The caller has checked the devices. `codec`
    overrides the session's CodecPolicy (the control)."""
    import jax
    kind = bench.load_module(HERE / "kinds" / f"{cell['traffic_file']['kind']}.py")
    rec = bench.Recorder(annotate=trace)
    watch = bench.CompileWatch()
    work = tempfile.mkdtemp(prefix="chipbench-")
    job = None
    try:
        rate = bench.write_fsync_rate(work)
        bench.log(f"store {work}: filesystem {bench.fs_type(work)}, "
                  f"write+fsync {rate / 2**20:.1f} MiB/s")
        job = bench.Job(cell["config_file"], cell["traffic_file"], seed,
                        work, rec, codec=codec)
        bench.log(f"state {job.state_bytes} bytes in {job.n_leaves} leaves; "
                  f"batch {job.gb} x {job.seq}")
        tdir = os.path.join(work, "trace")
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            out = kind.run(job, seconds, rec)
        finally:
            if trace:
                jax.profiler.stop_trace()
        (w,) = rec.named("window")
        setup_end = rec.named("setup_end")[0]["t0"]
        device = bench.device_info(chips)
        late = watch.between(w["t0"], w["t1"])
        bench.log(f"window {w['t1'] - w['t0']!r} s (asked {seconds}); "
                  f"compiles inside it: {len(late)} {late[:3]}")
        bench.log(f"device peak_bytes_in_use {device['memory_peak_bytes']}, "
                  f"host peak RSS {bench.host_rss_bytes()}")
        bench.log("window spans: " + " ".join(
            f"{s['name']}{'*' if s.get('overlapped') else ''}="
            f"{s['t1'] - s['t0']:.3f}" for s in rec.spans
            if w["t0"] <= s["t0"] <= w["t1"] and s["name"] != "window"))
        for stats in out.get("dumps", []):
            bench.log(f"dump counters {stats}")
        bench.log("store after the window: %d files, %d bytes"
                  % bench.store_summary(job.store))
        result = kind.check(job, out)
        job.free()
        t0 = time.perf_counter()
        gaps = reference.gaps(job.readings, job.reference())
        bench.log(f"reference steps {time.perf_counter() - t0!r} s")
        limits = cell["limits"]
        result["checks"].update({k: {"value": v, "limit": limits[k]}
                                 for k, v in gaps.items()})
        result["attempted"] += 1
        result["failed"] += any(v > limits[k] for k, v in gaps.items())
        r = {"spans": rec.spans, "setup_s": setup_end - t_start,
             "window": (w["t0"], w["t1"]), "job": job, "chips": chips,
             "device": device, "trace": None}
        if trace:
            import trace_reduce
            r["trace"] = trace_reduce.reduce_dir(tdir)
            device = dict(device, busy_s=r["trace"]["busy_s"],
                          window_s=r["trace"]["window_s"])
        return {**result, "rec": r, "device": device}
    finally:
        if job is not None:
            job.close()
        job = None
        gc.collect()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = bench.load_spec()
    cell = bench.load_cell(spec, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench.ROOT / ".jax_cache")
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bench.require_devices(cell["chips"])
    dev = jax.devices()[0]
    bench.log(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
              f"count {jax.device_count()}; cell {cell['name']} on "
              f"{cell['chips']} chip(s), seed {args.seed}")
    res = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), chips=cell["chips"],
                   t_start=T_START)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metric_names(spec, section, cell["name"]):
        v = bench.load_module(HERE / "metrics" / f"{m['name']}.py").read(
            res["rec"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    bench.print_checks(res["checks"])
    breakdown = None
    if args.trace:
        breakdown = {k: res["rec"]["trace"][k]
                     for k in ("device_ops", "idle_gaps")}
    print(bench.result_line(
        correct=is_correct(res),
        attempted=res["attempted"], failed=res["failed"], metrics=metrics,
        device=res["device"], checks=res["checks"], breakdown=breakdown),
        flush=True)


if __name__ == "__main__":
    main()
