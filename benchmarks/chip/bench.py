"""Shared pieces of the chip benchmark: the cell's files, the job, the
recorder, the reference fingerprint and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under this directory and is found by the
name ``BENCHMARK.json`` gives it:

  configs/<config>.json   sizes as run, with the published source, naming
                          its architecture's module ``references/<name>.py``
  traffic/<traffic>.json  parameters read by the generator ``kinds/<kind>.py``
  limits/<cell>.json      the limit of each number ``correct`` compares
  metrics/<metric>.py     a reader: ``read(rec) -> float | None``
"""
from __future__ import annotations

import contextlib
import hashlib
import importlib.util
import json
import os
import resource
import sys
import time
from pathlib import Path

import reference

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
GiB = 1 << 30
# the job's optimizer (repro.optim.OptConfig), which the reference follows
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup_steps": 10, "total_steps": 100_000}


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(spec: dict, name: str) -> dict:
    """The workload entry with its configuration file and traffic file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = json.loads((ROOT / conf["file"]).read_text())
    reference.module(cell["config_file"], f"configuration {conf['name']!r}")
    cell["traffic_file"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return cell


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ model
def model_config(conf: dict, model):
    """The program's ModelConfig for a configuration file: the program's
    config of the architecture with every size the file states
    (`model.program_fields`, `model` the file's reference module), checked
    key by key against what the file says the reference runs (``as_run``)."""
    from repro import configs
    fields = model.program_fields(conf)
    cfg = configs.get_config(fields["name"]).replace(**fields)
    run, said = conf["as_run"], model.as_run_readings(cfg)
    for key in sorted(set(run) | set(said)):
        if run.get(key) != said.get(key):
            raise ValueError(f"{fields['name']}: as_run {key}={run.get(key)} "
                             f"in the configuration file, {said.get(key)} in "
                             f"the program")
    return cfg


# --------------------------------------------------------------- recorder
class Recorder:
    """Host-clock spans from the benchmark's own files. With
    ``annotate`` each span is also a TraceAnnotation, so the profiler's
    trace shows what the host was doing in a device gap."""

    def __init__(self, annotate: bool = False):
        self.spans: list = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            rec = {"name": name, "t0": t0, **attrs}
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()
                self.spans.append(rec)

    def add(self, name: str, t0: float, t1: float, **attrs):
        self.spans.append({"name": name, "t0": t0, "t1": t1, **attrs})

    def named(self, name: str) -> list:
        return [s for s in self.spans if s["name"] == name]


class CompileWatch:
    """Backend compiles seen through jax.monitoring (cache hits included):
    none may fall inside the measured window."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), kw.get("fun_name", "?"),
                                secs))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]


# ---------------------------------------------------------------- devices
def require_devices(chips: int):
    """Exit non-zero, printing no result, without `chips` TPU devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] needs {chips} TPU device(s); JAX has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def host_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds `path` (from /proc/mounts)."""
    best, kind = "", "unknown"
    real = os.path.realpath(path)
    try:
        with open("/proc/mounts") as f:
            for line in f:
                parts = line.split()
                if len(parts) >= 3 and (real == parts[1] or real.startswith(
                        parts[1].rstrip("/") + "/")) and len(parts[1]) > len(best):
                    best, kind = parts[1], parts[2]
    except OSError:
        pass
    return kind


def write_fsync_rate(root: str, nbytes: int = 256 << 20,
                     file_bytes: int = 32 << 20) -> float:
    """Bytes/s of write + fsync into `root`, in files of `file_bytes`."""
    buf = os.urandom(file_bytes)
    paths = [os.path.join(root, f"probe_{i}") for i in range(nbytes // file_bytes)]
    t0 = time.perf_counter()
    for p in paths:
        with open(p, "wb") as f:
            f.write(buf)
            f.flush()
            os.fsync(f.fileno())
    dt = time.perf_counter() - t0
    for p in paths:
        os.remove(p)
    return nbytes / dt


# ------------------------------------------------------------- job pieces
def seeds(seed: int):
    """(numpy Generator, a 31-bit PRNG key) from any --seed."""
    import numpy as np
    rng = np.random.default_rng(int(seed))
    return rng, int(rng.integers(0, 2**31 - 1))


class Corpus:
    """The job's token corpus, made by the benchmark from the seed and
    written in the layout the program's TokenDataset reads, so that the
    benchmark knows which tokens every step must be fed."""
    SHARDS = 4
    TOKENS_PER_SHARD = 1 << 20

    def __init__(self, root: str, vocab_size: int, key: int):
        import numpy as np
        rng = np.random.default_rng(key)
        self.root, self.vocab_size, self.key = root, int(vocab_size), key
        self.tokens = rng.integers(0, vocab_size, self.SHARDS
                                   * self.TOKENS_PER_SHARD, dtype=np.int32)
        os.makedirs(root, exist_ok=True)
        for i, shard in enumerate(np.split(self.tokens, self.SHARDS)):
            np.save(os.path.join(root, f"shard_{i:05d}.tokens.npy"), shard)
        with open(os.path.join(root, "dataset.json"), "w") as f:
            json.dump({"vocab_size": self.vocab_size, "seed": key,
                       "num_shards": self.SHARDS,
                       "tokens_per_shard": self.TOKENS_PER_SHARD}, f)

    def dataset(self):
        from repro.data import TokenDataset
        return TokenDataset(self.root, vocab_size=self.vocab_size,
                            seed=self.key, num_shards=self.SHARDS,
                            tokens_per_shard=self.TOKENS_PER_SHARD)

    def batch(self, step: int, global_batch: int, seq_len: int):
        """Reference: sequence g of the stream is tokens
        [g*(S+1), (g+1)*(S+1)) of the corpus, wrapping at its end."""
        import numpy as np
        n = seq_len + 1
        start = step * global_batch * n
        idx = (start + np.arange(global_batch * n)) % self.tokens.size
        return self.tokens[idx].reshape(global_batch, n)


def batch_digest(batch) -> str:
    import numpy as np
    return hashlib.sha1(np.ascontiguousarray(batch).tobytes()).hexdigest()


def layout(lm, devices=None):
    """(mesh, state shardings, batch sharding) of the job on one chip, a
    (data=1, model=1) mesh over the first of `devices` (default: this
    process's devices)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed import sharding as shd
    from repro.training.train_loop import train_state_pspecs
    mesh = Mesh(np.asarray((devices or jax.devices())[:1]).reshape(1, 1),
                ("data", "model"))
    sps = shd.named(mesh, train_state_pspecs(lm, shd.make_rules(lm.cfg, mesh)))
    return mesh, sps, NamedSharding(mesh, P("data", None))


def with_shardings(struct, shardings):
    import jax
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), struct, shardings)


def compile_step(lm, opt_cfg, state_struct, batch_struct):
    """The job's step program (make_train_step, donated state, as
    launch/train.py jits it), compiled ahead of time for these shardings."""
    import jax
    from repro.training.train_loop import make_train_step
    out_sh = jax.tree.map(lambda a: a.sharding, state_struct)
    fn = jax.jit(make_train_step(lm, opt_cfg), donate_argnums=(0,),
                 out_shardings=(out_sh, None))
    return fn.lower(state_struct, batch_struct).compile()


def init_state(init, shardings, key: int):
    """The train state made on the device from the seed: the weights by
    `init` (the reference's jitted init_params, one call, so that the
    reference and the change readings start from these very bits), Adam's
    m and v at zero, step 0, in the shardings it is trained in."""
    import jax
    import jax.numpy as jnp
    params = init(jax.random.PRNGKey(key))
    return jax.jit(lambda p: {
        "params": p, "opt": {"m": jax.tree.map(jnp.zeros_like, p),
                             "v": jax.tree.map(jnp.zeros_like, p)},
        "step": jnp.zeros((), jnp.int32)}, out_shardings=shardings)(params)


# ------------------------------------------------------ reference digest
def _fmix32(h):
    import jax.numpy as jnp
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def leaf_fingerprint(x):
    """Two wrapping uint32 sums over a leaf's bit patterns, each element
    mixed with its flat position through a bijection, so any single changed
    element changes both sums, and a swap of two elements changes them too.
    Integer sums do not depend on the order of reduction, so the same
    values give the same pair on any mesh."""
    import jax
    import jax.numpy as jnp
    size = jnp.dtype(x.dtype).itemsize
    bits = jax.lax.bitcast_convert_type(
        x, {4: jnp.uint32, 2: jnp.uint16, 1: jnp.uint8}[size])
    bits = bits.astype(jnp.uint32).reshape(-1)
    pos = jax.lax.iota(jnp.uint32, bits.size)
    k1 = _fmix32(pos * jnp.uint32(2) + jnp.uint32(1))
    k2 = _fmix32(pos ^ jnp.uint32(0x9E3779B9))
    h1 = _fmix32(bits ^ k1)
    h2 = _fmix32((bits + k2) ^ jnp.uint32(0x27D4EB2F))
    return jnp.stack([h1.sum(dtype=jnp.uint32), h2.sum(dtype=jnp.uint32)])


def compile_fingerprint(state_struct):
    """[n_leaves, 2] uint32 fingerprints of a state, replicated."""
    import jax
    import jax.numpy as jnp

    def fp(tree):
        return jnp.stack([leaf_fingerprint(x) for x in jax.tree.leaves(tree)])
    return jax.jit(fp).lower(state_struct).compile()


# ------------------------------------------------------------- the result
def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown: dict | None = None) -> str:
    """The last line of standard output. `checks` (each number compared,
    with its limit) comes last, and is also printed on standard error."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict):
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)


# ---------------------------------------------------------------- the job
class Job:
    """One training job as ``launch/train.py`` runs it, built in set-up:
    the state made on the device from the seed, the step program compiled
    for it, the data iterator over the benchmark's corpus, and the sessions
    it checkpoints through. ``codec`` overrides the session's CodecPolicy
    (the control's lower-precision path)."""

    def __init__(self, conf: dict, traffic: dict, seed: int, workdir: str,
                 rec: Recorder, codec: dict | None = None):
        import jax
        from repro.data import DataIterator
        from repro.models.model import LM
        from repro.optim import OptConfig
        from repro.training.train_loop import abstract_train_state
        self.conf, self.traffic, self.rec = conf, traffic, rec
        self.model = reference.module(conf)
        self.cfg = model_config(conf, self.model)
        self.codec = codec or {}
        self.gb, self.seq = traffic["global_batch"], traffic["seq_len"]
        self.rng, self.key = seeds(seed)
        self.store = os.path.join(workdir, "store")
        self.lm = LM(self.cfg)
        self.opt = OptConfig(**OPT)
        with rec.span("corpus"):
            self.corpus = Corpus(os.path.join(workdir, "data"),
                                 self.cfg.vocab_size, self.key)
            self.ds = self.corpus.dataset()
        self.abstract = abstract_train_state(self.lm)
        ref = jax.eval_shape(lambda: self.model.init_params(
            conf, jax.random.PRNGKey(0)))
        if ref != self.abstract["params"]:
            raise ValueError("the reference's weight tree differs from the "
                             "program's")
        self.n_leaves = len(jax.tree.leaves(self.abstract))
        with rec.span("compile"):
            self.mesh, self.shardings, self.batch_sharding = layout(self.lm)
            self.struct = with_shardings(self.abstract, self.shardings)
            batch = {"tokens": jax.ShapeDtypeStruct(
                (self.gb, self.seq + 1), jax.numpy.int32,
                sharding=self.batch_sharding)}
            self.step = compile_step(self.lm, self.opt, self.struct, batch)
            self.fp = compile_fingerprint(self.struct)
            self.init = reference.jit_init(self.model, conf)
            self.norms = jax.jit(reference.leaf_norms)
            self.change = jax.jit(lambda p, p0: reference.leaf_norms(
                jax.tree.map(jax.numpy.subtract, p, p0)))
        with rec.span("init"):
            self.state = init_state(self.init, self.shardings, self.key)
            jax.block_until_ready(self.state)
        self.state_bytes = sum(a.size * a.dtype.itemsize
                               for a in jax.tree.leaves(self.abstract))
        self.gstep = 0          # global steps trained
        self.fed: list = []     # (global step, digest of the batch fed)
        self.readings: dict = {}
        self.it = DataIterator(self.ds, global_batch=self.gb,
                               seq_len=self.seq)
        self.it.start_prefetch()
        self.sess = None

    # ------------------------------------------------------------ session
    def session_config(self):
        from repro.api import (CodecPolicy, MigrationPolicy, PreemptionPolicy,
                               RetentionPolicy, SessionConfig)
        from repro.distributed.sharding import mesh_topology
        return SessionConfig(
            root=f"file://{self.store}",
            retention=RetentionPolicy(keep_last=1),
            codec=CodecPolicy(**self.codec),
            preemption=PreemptionPolicy(install_signals=True),
            migration=MigrationPolicy(arch=self.cfg.name,
                                      topology=mesh_topology(self.mesh)))

    def open_session(self):
        from repro.api import CheckpointSession
        self.sess = CheckpointSession(self.session_config()).__enter__()
        return self.sess

    def close_session(self):
        if self.sess is not None:
            self.sess.close()
            self.sess = None

    # --------------------------------------------------------------- step
    def train_step(self):
        """Feed the iterator's next batch to the step; block on the loss,
        as launch/train.py does."""
        import jax
        tokens = self.it.next_prefetched()
        self.fed.append((self.gstep, batch_digest(tokens)))
        batch = {"tokens": jax.device_put(tokens, self.batch_sharding)}
        self.state, m = self.step(self.state, batch)
        loss = jax.block_until_ready(m["loss"])
        self.gstep += 1
        return loss

    def fingerprint(self, state=None):
        import numpy as np
        return np.asarray(self.fp(self.state if state is None else state))

    def warm(self) -> float:
        """Drive the job through its first reference.STEPS steps, reading
        what the reference is compared on: each step's loss, each leaf's
        norm of the first clipped gradient (from Adam's m after one step)
        and of the weights' change over the steps. Returns the last step's
        seconds (the step time the traffic plans with)."""
        import jax
        import numpy as np
        loss, grad, dt = [], None, 0.0
        for _ in range(reference.STEPS):
            t0 = time.perf_counter()
            loss.append(float(self.train_step()))
            dt = time.perf_counter() - t0
            if grad is None:
                grad = np.asarray(self.norms(self.state["opt"]["m"])) \
                    / (1.0 - OPT["b1"])
        change = np.asarray(self.change(
            self.state["params"], self.init(jax.random.PRNGKey(self.key))))
        self.readings = {"loss": np.asarray(loss), "grad": grad,
                         "change": change}
        self.fingerprint()
        return dt

    def reference(self, mode: str = "bf16", rows: int | None = None) -> dict:
        """The reference's first steps from the job's initial weights on
        the reference stream (reference.train). Run once the program's
        state is freed."""
        import jax
        batches = [self.corpus.batch(g, self.gb, self.seq)
                   for g in range(reference.STEPS)]
        return reference.train(self.model, self.conf, OPT,
                               jax.random.PRNGKey(self.key), batches,
                               mode=mode, rows=rows)

    def batch_mismatches(self) -> int:
        """Steps whose batch differs from the reference stream."""
        return sum(d != batch_digest(self.corpus.batch(g, self.gb, self.seq))
                   for g, d in self.fed)

    def leaf_mismatches(self, want, got) -> int:
        import numpy as np
        return int(np.any(np.asarray(want) != np.asarray(got), axis=1).sum())

    def free(self):
        """Drop the program's state and session, so the reference has the
        device."""
        import gc
        self.state = None
        self.close_session()
        gc.collect()

    def close(self):
        self.it.stop_prefetch()
        self.close_session()


# ------------------------------------------------------------ for readers
def durations(rec: dict, name: str) -> list:
    """Seconds of each span `name`, less what it excluded (reference work
    inside it that the job itself would not do)."""
    return [s["t1"] - s["t0"] - s.get("excluded", 0.0)
            for s in rec["spans"] if s["name"] == name]


def mean_of(rec: dict, name: str):
    """Mean of `name`'s durations, None where the run has none."""
    d = durations(rec, name)
    return sum(d) / len(d) if d else None


def store_summary(root: str) -> tuple:
    """(files, bytes) under a store directory."""
    n = size = 0
    for dirpath, _, files in os.walk(root):
        for f in files:
            n += 1
            size += os.path.getsize(os.path.join(dirpath, f))
    return n, size
