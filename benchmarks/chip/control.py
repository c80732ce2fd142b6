#!/usr/bin/env python3
"""Readings that the limits of a cell's `correct` are set from, at the
cell's own size, several seeds in one process:

- ``sound``: the program's first steps against the plain reference
  (the lower readings);
- ``int8`` (the control): the reference put in the program's place with
  its weight matmuls in int8, the step below the bfloat16 the
  configuration states;
- ``half``: the fault of a step that leaves out half of the batch and takes
  the mean over the rest (the reference on the first half of the rows).

A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
definition and needs no run. With ``--codec`` the seeds instead run whole
(a ``--seconds`` window) with the program's own lower-precision checkpoint
path, CodecPolicy(params="bf16", optimizer="bf16"), which must come out not
correct on ``leaf_mismatch``.

    python3 benchmarks/chip/control.py --workload <cell> --seeds <n> ... \\
        [--faults <k>] [--codec --seconds <s>]

``--faults k`` reads int8 and half on the first k seeds only. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CODEC = {"params": "bf16", "optimizer": "bf16"}


def readings(cell: dict, seed: int, faults: bool) -> dict:
    """Set-up and first steps of one run, then the reference, the control
    and the fault, each compared with the reference."""
    work = tempfile.mkdtemp(prefix="chipbench-")
    job = bench.Job(cell["config_file"], cell["traffic_file"], seed, work,
                    bench.Recorder())
    try:
        job.warm()
        job.free()
        ref = job.reference()
        out = {"sound": reference.gaps(job.readings, ref)}
        if faults:
            out["int8"] = reference.gaps(job.reference(mode="int8"), ref)
            out["half"] = reference.gaps(job.reference(rows=job.gb // 2), ref)
        return out
    finally:
        job.close()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--codec", action="store_true")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(bench.load_spec(), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench.ROOT / ".jax_cache")
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench.require_devices(cell["chips"])
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        if args.codec:
            res = run.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, chips=cell["chips"],
                               t_start=t0, codec=CODEC)
            out = {"codec": {k: c["value"] for k, c in res["checks"].items()},
                   "correct": run.is_correct(res)}
        else:
            out = readings(cell, seed, i < args.faults)
        print(json.dumps({"control": cell["name"], "seed": seed, **out,
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
