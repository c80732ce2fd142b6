#!/usr/bin/env python3
"""Smoke run of the save -> preempt -> resume path on one TPU chip.

Drives the training job the checkpoint system exists to save, through its
own entry point (``repro.launch.train.main``), at qwen3-8b's published
widths: d_model 4096, 32 heads with 8 KV heads, head_dim 128, d_ff 12288.
Depth is cut to 2 of 36 layers and the vocabulary to an 8-way share
(18992 of 151936 rows), so the f32 params + Adam state fit one v5e with
the step's temporaries. Weights and data come from seeds.

Phases, all in this one process (the preemption is the process
signalling itself, so no second process ever needs the chip):

  reference   an uninterrupted run, losses logged every step;
  preempt     the same run with periodic async dumps, SIGTERM'd by a timer
              thread after step 3 -> migration image -> exit 85;
  resume      --resume to the same final step: losses must equal the
              reference's bit for bit;
  lazy        --resume --lazy-resume: same check, and the deferred state
              digest must be verified;
  codec       one delta8 and one bf16 save/restore through the Pallas
              codec kernels (CodecPolicy(device="on") and the plain
              encode_leaf/decode_leaf path): stored bytes must equal the
              impl="jnp" reference and decode stay within the codec bound.

``--four-chips`` runs only the layout change: the same state sharded over
a (data=2, model=2) mesh, one step, a dump, a restore onto (data=4,
model=1), bit-identical leaves that span the new mesh, and one step there
whose loss must match the old layout's within rounding (DESIGN.md §6.3).

Run:  python chip_smoke.py [--four-chips]

Refuses to run without a TPU. Checkpoint images (about 6.5 GB each) go
under ``.chip_smoke/`` next to this file and are deleted at the end. The
last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import resource
import shutil
import signal
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen3-8b"
LAYERS = 2
VOCAB_SHARE = 8
GLOBAL_BATCH = 4     # x SEQ_LEN: the step's temporaries fit beside the state
SEQ_LEN = 1024
STEPS = 8
CKPT_EVERY = 2
PREEMPT_AFTER = 3
# flat codec leaves from the smoke config: the wk projection, the
# embedding, and a length that is not a multiple of the codec block
CODEC_LEAVES = {"wk": (4096, 1024), "embed": (18992, 4096),
                "ragged": (1000, 1001)}
# continuation across meshes agrees only to rounding (DESIGN.md §6.3); the
# band examples/elastic_resize.py holds it to
LAYOUT_LOSS_RTOL = 1e-2
GiB = 1 << 30


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------ measurement
class CompileLog:
    """Backend compiles seen through jax.monitoring, with whether each came
    from the persistent cache: [(fun_name, seconds, cache_hit)]."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.events, self._hits, self._seen = [], 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == self.HIT:
            self._hits += 1

    def _duration(self, event, secs, **kw):
        if event == self.COMPILE:
            hit, self._seen = self._hits > self._seen, self._hits
            self.events.append((kw.get("fun_name", "?"), secs, hit))

    def take(self):
        out, self.events = self.events, []
        return out


def memory_line() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    hbm = f"{peak / GiB:.3f} GiB" if peak is not None else "not reported"
    return f"device peak_bytes_in_use {hbm}, host peak RSS {rss / GiB:.3f} GiB"


def live_device_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


class Tee:
    """stdout wrapper that keeps the trainer's lines and, when asked, sends
    SIGTERM to this process from a timer thread once step k is logged."""

    def __init__(self, out, preempt_after: int | None = None):
        self.out, self.lines = out, []
        self.mark = (f'"step": {preempt_after},'
                     if preempt_after is not None else None)

    def write(self, s):
        self.out.write(s)
        self.lines.append(s)
        if self.mark and s.startswith("[train] {") and self.mark in s:
            self.mark = None
            threading.Timer(0.0, os.kill,
                            (os.getpid(), signal.SIGTERM)).start()
        return len(s)

    def flush(self):
        self.out.flush()

    def saw(self, text: str) -> bool:
        return any(text in s for s in self.lines)


def run_train(label: str, argv: list, compiles: CompileLog,
              preempt_after: int | None = None):
    """One in-process train.main call -> (exit code, Tee)."""
    gc.collect()
    live = live_device_bytes()
    say(f"{label}: device arrays live before start {live / GiB:.3f} GiB")
    assert live < GiB, f"{label}: a previous state is still on the device"
    tee = Tee(sys.stdout, preempt_after)
    code = 0
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        try:
            train.main(argv)
        except SystemExit as e:
            code = e.code
    wall = time.perf_counter() - t0
    evs = compiles.take()
    step = [f"{s:.2f}s {'cache hit' if hit else 'compiled'}"
            for name, s, hit in evs if "train_step" in name]
    say(f"{label}: exit {code}, wall {wall:.1f}s; backend compile "
        f"{sum(s for _, s, _ in evs):.2f}s over {len(evs)} programs; "
        f"train_step: {', '.join(step) or 'none'}")
    say(f"{label}: {memory_line()}")
    return code, tee, evs


def losses(path: Path) -> dict:
    return {r["step"]: r["loss"] for r in json.loads(path.read_text())}


def compare_losses(label: str, ref: dict, got: dict):
    steps = sorted(got)
    assert steps and all(s in ref for s in steps), (label, steps, sorted(ref))
    diff = max(abs(got[s] - ref[s]) for s in steps)
    bitwise = all(got[s] == ref[s] for s in steps)
    say(f"{label}: steps {steps[0]}..{steps[-1]} loss "
        f"{'bitwise equal to the reference' if bitwise else 'NOT bitwise'}"
        f" (max |dloss| {diff!r})")
    assert bitwise, f"{label}: continuation differs from the reference"


# ------------------------------------------------------------ phases
def train_phases(base: list, work: Path, compiles: CompileLog):
    ck = str(work / "ck")
    ref_m, res_m, lazy_m = (work / f"{n}.json" for n in ("ref", "res", "lazy"))

    code, _, _ = run_train("reference", base + ["--metrics-file", str(ref_m)],
                           compiles)
    assert code == 0, f"reference run exited {code}"
    ref = losses(ref_m)

    code, tee, _ = run_train(
        "preempt", base + ["--ckpt-dir", ck, "--ckpt-every", str(CKPT_EVERY),
                           "--ckpt-async"],
        compiles, preempt_after=PREEMPT_AFTER)
    assert code == 85, f"preempted run exited {code}, expected 85"
    assert tee.saw("preemption (SIGTERM)") and tee.saw("durable"), \
        "no migration image was reported"
    say("preempt: SIGTERM -> migration image -> exit 85")

    code, tee, evs = run_train(
        "resume", base + ["--ckpt-dir", ck, "--resume", "--metrics-file",
                          str(res_m)], compiles)
    assert code == 0 and tee.saw("resumed from"), f"resume exited {code}"
    compare_losses("resume", ref, losses(res_m))
    step_hits = [hit for name, _, hit in evs if "train_step" in name]
    assert step_hits and all(step_hits), \
        "resume: the step program was compiled again, not read from the cache"

    code, tee, _ = run_train(
        "lazy", base + ["--ckpt-dir", ck, "--resume", "--lazy-resume",
                        "--metrics-file", str(lazy_m)], compiles)
    assert code == 0, f"lazy resume exited {code}"
    assert tee.saw("digest verified"), "lazy resume did not verify the digest"
    compare_losses("lazy", ref, losses(lazy_m))
    shutil.rmtree(ck)


def assert_kernel_on_path(fn, args, block):
    """The codec op compiles to a Pallas kernel on this device."""
    hlo = fn.lower(*args, block=block).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{fn.__name__}: no Pallas kernel"


def codec_phase(codec: str, leaves: dict, work: Path, seed: int = 0):
    """One save + restore of optimizer moments under a lossy codec, through
    the device stage and the plain encode_leaf path."""
    from repro.api import (CheckpointSession, CodecPolicy, DumpRequest,
                           RestoreRequest, SessionConfig)
    from repro.core import chunking
    from repro.core.compression import CODEC_BLOCK, encode_leaf
    from repro.core.restore import read_manifest
    from repro.kernels.ckpt_codec import ops

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 2 * len(leaves)))
    m1, m2 = {}, {}
    for name, shape in leaves.items():
        m1[name] = 0.01 * jax.random.normal(next(keys), shape, jnp.float32)
        m2[name] = m1[name] + 1e-4 * jax.random.normal(next(keys), shape,
                                                       jnp.float32)
    root = work / f"codec_{codec}"
    sess = CheckpointSession(SessionConfig(
        root=str(root), codec=CodecPolicy(optimizer=codec, device="on")))
    if codec == "delta8":       # the baseline image the delta refers to
        sess.dump(DumpRequest(state={"opt": {"m": m1}}, step=1))
    r = sess.dump(DumpRequest(state={"opt": {"m": m2}}, step=2))
    assert r.stats["leaves_device"] == len(leaves), r.stats
    recs = {rec["path"]: rec
            for rec in read_manifest(sess.tier, r.image_id)["leaves"]}
    back = sess.restore(RestoreRequest()).state["opt"]["m"]

    for name in leaves:
        x, p = m2[name].reshape(-1), m1[name].reshape(-1)
        n = x.size
        rec = recs[f"opt/m/{name}"]
        assert rec["codec_meta"].get("encoder") == "device", rec
        stored = chunking.assemble_leaf(rec, sess.tier.read_chunk)
        xh = np.asarray(m2[name])
        if codec == "delta8":
            fn = ops.delta_encode_digest
            args = (x, p)
            q, s, _d, h1, h2 = (np.asarray(a) for a in fn(
                x, p, block=CODEC_BLOCK, impl="jnp"))
            want = np.concatenate([s.view(np.int8), q.reshape(-1)])
            digest = ops.fold_digest(h1, h2, scale_bits=s, n=n)
            plain, _ = encode_leaf(xh, "delta8", np.asarray(m1[name]))
            # half a quantization step per block, plus float rounding
            bound = (np.repeat(s / 2, CODEC_BLOCK)[:n] * (1 + 1e-6)
                     + 4 * np.spacing(np.abs(xh.reshape(-1))))
        else:
            fn = ops.bf16_encode_digest
            args = (x,)
            y, h1, h2 = (np.asarray(a) for a in fn(
                x, block=CODEC_BLOCK, impl="jnp"))
            want = y.reshape(-1)[:n]
            digest = ops.fold_digest(h1, h2, n=n)
            plain, _ = encode_leaf(xh, "bf16")
            bound = np.abs(xh.reshape(-1)) * 2.0 ** -8
        assert_kernel_on_path(fn, args, CODEC_BLOCK)
        for what, got in (("device stage", stored), ("encode_leaf", plain)):
            same = (np.ascontiguousarray(got).view(np.uint8).tobytes()
                    == np.ascontiguousarray(want).view(np.uint8).tobytes())
            assert same, f"{codec} {name}: {what} bytes != jnp reference"
        assert rec["codec_meta"]["digest"] == digest, (codec, name)
        err = np.abs(np.asarray(back[name], np.float32).reshape(-1)
                     - xh.reshape(-1))
        assert np.all(err <= bound), \
            f"{codec} {name}: decode error {err.max()} beyond the codec bound"
        say(f"codec {codec} {name} {tuple(leaves[name])}: stored "
            f"{stored.nbytes} B == jnp reference (device stage and "
            f"encode_leaf), digest {digest}, max decode error "
            f"{float(err.max())!r} within bound")
    sess.close()
    shutil.rmtree(root)


def four_chip_phase(cfg, work: Path, *, global_batch: int, seq_len: int,
                    seed: int = 0):
    """Dump a (data=2, model=2) sharded train state, restore it onto
    (data=4, model=1), and continue one step on each layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.api import (CheckpointSession, DumpRequest, RestoreRequest,
                           SessionConfig)
    from repro.data import DataIterator, TokenDataset
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import LM
    from repro.optim import OptConfig
    from repro.training.train_loop import (abstract_train_state,
                                           init_train_state, make_train_step,
                                           train_state_pspecs)

    lm = LM(cfg)
    opt = OptConfig(lr=3e-4, warmup_steps=10, total_steps=STEPS)
    ds = TokenDataset(str(work / "data"), vocab_size=cfg.vocab_size,
                      seed=seed)
    it = DataIterator(ds, global_batch=global_batch, seq_len=seq_len)

    def layout(shape):
        mesh = make_test_mesh(shape, ("data", "model"))
        sps = shd.named(mesh, train_state_pspecs(
            lm, shd.make_rules(cfg, mesh)))
        bsp = NamedSharding(mesh, P("data", None))
        step = jax.jit(make_train_step(lm, opt), in_shardings=(sps, bsp),
                       out_shardings=(sps, None), donate_argnums=(0,))
        return mesh, sps, bsp, step

    def batch(i, bsp):
        return {"tokens": jax.device_put(jnp.asarray(it.batch_at(i)), bsp)}

    mesh_a, sps_a, bsp_a, step_a = layout((2, 2))
    state = jax.jit(lambda: init_train_state(lm, jax.random.PRNGKey(seed)),
                    out_shardings=sps_a)()
    state, m = step_a(state, batch(0, bsp_a))
    say(f"four-chip: step 1 on mesh {dict(mesh_a.shape)} loss "
        f"{float(m['loss'])!r}")
    sess = CheckpointSession(SessionConfig(root=str(work / "ck4")))
    sess.dump(DumpRequest(state=state, step=1,
                          topology=shd.mesh_topology(mesh_a)))
    dumped = jax.device_get(state)
    state, m = step_a(state, batch(1, bsp_a))
    loss_a = float(m["loss"])
    del state, m
    gc.collect()

    mesh_b, sps_b, bsp_b, step_b = layout((4, 1))
    res = sess.restore(RestoreRequest(target_struct=abstract_train_state(lm),
                                      shardings=sps_b))
    state = res.state
    n = 0
    for (path, got), want, sh in zip(
            jax.tree_util.tree_leaves_with_path(state),
            jax.tree.leaves(dumped), jax.tree.leaves(sps_b)):
        assert len(got.sharding.device_set) == mesh_b.devices.size, path
        assert got.sharding.is_equivalent_to(sh, got.ndim), path
        assert np.array_equal(np.asarray(got), want), path
        n += 1
    say(f"four-chip: restored {n} leaves onto mesh {dict(mesh_b.shape)}: "
        f"bit-identical, each spanning {mesh_b.devices.size} devices")
    del dumped
    state, m = step_b(state, batch(1, bsp_b))
    loss_b = float(m["loss"])
    diff = abs(loss_a - loss_b)
    say(f"four-chip: step 2 loss {loss_a!r} on {dict(mesh_a.shape)} vs "
        f"{loss_b!r} on {dict(mesh_b.shape)}: |dloss| {diff!r} "
        f"(band rtol {LAYOUT_LOSS_RTOL})")
    assert diff <= LAYOUT_LOSS_RTOL * abs(loss_a), "layouts diverged"
    sess.close()
    shutil.rmtree(work / "ck4")


# ------------------------------------------------------------ entry point
def smoke_config():
    full = configs.get_config(ARCH)
    return full.replace(num_layers=LAYERS,
                        vocab_size=full.vocab_size // VOCAB_SHARE)


def train_argv(cfg, work: Path, *, global_batch: int, seq_len: int) -> list:
    return ["--arch", ARCH, "--full", "--layers", str(cfg.num_layers),
            "--vocab", str(cfg.vocab_size), "--steps", str(STEPS),
            "--global-batch", str(global_batch), "--seq-len", str(seq_len),
            "--log-every", "1", "--seed", "0",
            "--data-dir", str(work / "data")]


def describe(cfg, *, global_batch: int, seq_len: int):
    from repro.models.model import LM
    from repro.training.train_loop import abstract_train_state
    full = configs.get_config(ARCH)
    state = abstract_train_state(LM(cfg))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    nparams = sum(a.size for a in jax.tree.leaves(state["params"]))
    say(f"config {ARCH} at published widths: d_model {cfg.d_model}, "
        f"heads {cfg.num_heads} (kv {cfg.num_kv_heads}), head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}")
    say(f"cut: layers {cfg.num_layers} of {full.num_layers}; vocabulary "
        f"{cfg.vocab_size} of {full.vocab_size} (1/{VOCAB_SHARE} share, "
        f"padded to {cfg.padded_vocab}); batch {global_batch} x seq "
        f"{seq_len}")
    say(f"train state: {nparams} params, {nbytes} bytes "
        f"({nbytes / GiB:.3f} GiB: f32 params + Adam m/v)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded dump and the resume onto "
                         "another layout, on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    say(f"compile cache: {use_compile_cache()}")

    cfg = smoke_config()
    shape = dict(global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN)
    describe(cfg, **shape)
    work = ROOT / ".chip_smoke"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir()
    free = shutil.disk_usage(work).free
    say(f"work dir {work}: {free / GiB:.1f} GiB free on its disk")
    try:
        if args.four_chips:
            four_chip_phase(cfg, work, **shape)
        else:
            compiles = CompileLog()
            train_phases(train_argv(cfg, work, **shape), work, compiles)
            for codec in ("delta8", "bf16"):
                codec_phase(codec, CODEC_LEAVES, work)
        say(f"all phases passed; {memory_line()}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
