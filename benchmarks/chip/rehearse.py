#!/usr/bin/env python3
"""Compile each cell's step program, and the reference's step that
``correct`` runs after the window, for one chip of a described TPU v5e
(v5e:2x2), with no chip attached, and print what the compiler says each
needs.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]
        [--batch 1,2,4,8]

``--batch`` overrides the cell's global batch, to find the largest that
compiles beside the state. Nothing runs and nothing is timed: a compile
that passes here is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402
import reference  # noqa: E402


def rehearse(cell: dict, batch: int, devices) -> dict:
    import jax
    from repro.models.model import LM
    from repro.optim import OptConfig
    from repro.training.train_loop import abstract_train_state
    t, conf = cell["traffic_file"], cell["config_file"]
    model = reference.module(conf)
    lm = LM(bench.model_config(conf, model))
    _, sps, bsp = bench.layout(lm, devices)
    struct = bench.with_shardings(abstract_train_state(lm), sps)
    tokens = jax.ShapeDtypeStruct((batch, t["seq_len"] + 1),
                                  jax.numpy.int32, sharding=bsp)
    out = {"cell": cell["name"], "batch": batch, "seq_len": t["seq_len"]}
    t0 = time.perf_counter()
    try:
        c = bench.compile_step(lm, OptConfig(**bench.OPT), struct,
                               {"tokens": tokens})
    except Exception as e:  # noqa: BLE001 - report what the compiler refused
        return dict(out, refused=f"{type(e).__name__}: "
                    f"{str(e).splitlines()[0][:300]}")
    out["step"] = memory(c, t0)
    t0 = time.perf_counter()
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sps["params"]["final_norm"]),
        struct["params"])
    ref = jax.jit(reference.step_fn(model, conf, bench.OPT),
                  donate_argnums=(0, 1, 2))
    out["reference"] = memory(ref.lower(
        params, params, params, jax.ShapeDtypeStruct((), jax.numpy.int32),
        tokens).compile(), t0)
    return out


def memory(compiled, t0: float) -> dict:
    m = compiled.memory_analysis()
    return {"compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "per_device_gib": round((m.argument_size_in_bytes
                                     + m.temp_size_in_bytes) / bench.GiB, 3)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--batch", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = bench.load_spec()
    for name in args.cells or [w["name"] for w in spec["workloads"]]:
        cell = bench.load_cell(spec, name)
        for b in ([int(x) for x in args.batch.split(",")] if args.batch
                  else [cell["traffic_file"]["global_batch"]]):
            print(rehearse(cell, b, list(topo.devices)), flush=True)


if __name__ == "__main__":
    main()
