"""The shared part of the plain references of the training step.

Each configuration file names its architecture's module under
``references/`` (``"reference": "<module>"``); that module holds what is
particular to the architecture (the program's config from the file's keys,
the weights, the loss, the FLOP count) and builds on the pieces here, which
every architecture shares: bfloat16 matmuls with float32 accumulation (and
the int8 control), RMSNorm, rotary embedding, blocked causal attention, a
SwiGLU MLP, the chunked cross-entropy over the real vocabulary, global-norm
clipping, warmup-cosine learning rate and AdamW. Nothing here or there
imports the program.

The benchmark makes the job's initial weights with the module's
``init_params`` from the seed, so the reference starts from the same
weights without taking them from the program. ``train`` runs the first
steps and returns what ``gaps`` compares with the program's readings of the
same steps: each step's loss, each leaf's norm of the first (clipped)
gradient, and each leaf's norm of the parameters' change over the steps.

``mode="int8"`` is the control: every weight matmul with both operands
rounded to int8 (one scale per tensor), the step below bfloat16.
"""
from __future__ import annotations

import functools
import importlib
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32
STEPS = 3
MODULES = Path(__file__).resolve().parent / "references"


def module(conf: dict, name: str = "the configuration"):
    """The architecture's module that configuration file `conf` names
    (``references/<conf["reference"]>.py``). `name` says which file, for
    the message when it names none or one that is not there."""
    ref = conf.get("reference")
    if not ref:
        raise ValueError(f"{name} names no reference module: give it a key "
                         f"\"reference\" that names one of "
                         f"{sorted(p.stem for p in MODULES.glob('*.py'))}")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(ref)) \
            or not (MODULES / f"{ref}.py").is_file():
        raise ValueError(f"{name} names reference module {ref!r}, which is "
                         f"not under {MODULES.name}/")
    return importlib.import_module(f"references.{ref}")


# ------------------------------------------------------------------ weights
def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_from_specs(specs, key):
    """Float32 weights of a tree of (shape, std) leaves from a PRNG key:
    normal(0, std), the i-th leaf in tree order from fold_in(key, i), or
    ones where std is None."""
    flat, tree = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    out = []
    for i, (shape, std) in enumerate(flat):
        if std is None:
            out.append(jnp.ones(shape, F32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         F32) * std)
    return jax.tree_util.tree_unflatten(tree, out)


def jit_init(model, conf: dict):
    """`model`'s init_params compiled for `conf`: the one program that makes
    the initial weights, for the job and for the reference alike."""
    return jax.jit(functools.partial(model.init_params, conf))


def leaf_norms(tree):
    """Float32 L2 norm of each leaf, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


# ------------------------------------------------------------ model pieces
def _int8(x):
    """Round to int8 with one scale per tensor; the gradient passes through."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(F32))), 1e-30) / 127.0
    q = (jnp.clip(jnp.round(x.astype(F32) / s), -127, 127) * s).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def mm(eq, a, w, mode):
    """A weight matmul: bfloat16 operands (int8 in the control)."""
    a, w = a.astype(BF16), w.astype(BF16)
    if mode == "int8":
        a, w = _int8(a), _int8(w)
    return jnp.einsum(eq, a, w)


def rms(x, scale, eps):
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * scale.astype(F32)).astype(x.dtype)


def rope(x, theta):
    """Rotate the two halves of each head by position x frequency."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(q, k, v, block=256):
    """Causal softmax attention, one block of queries at a time."""
    B, S, H, hd = q.shape
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    q = q * jnp.asarray(hd ** -0.5, q.dtype)
    nb = -(-S // block)
    qb = jnp.pad(q, ((0, 0), (0, nb * block - S), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(B, nb, block, H, hd), 1, 0)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, preferred_element_type=F32)
        rows = i * block + jnp.arange(block)
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(BF16), v,
                          preferred_element_type=F32).astype(q.dtype)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return jnp.moveaxis(out, 0, 1).reshape(B, nb * block, H, hd)[:, :S]


def swiglu(p, h, mode):
    up = mm("bsd,df->bsf", h, p["w_up"], mode)
    gate = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"], mode))
    return mm("bsf,fd->bsd", gate * up, p["w_down"], mode)


def cross_entropy(x, labels, head, vocab, mode, chunk=512):
    """Mean next-token loss; logits over the padded vocabulary (the head's
    columns) with the rows past `vocab` masked, one chunk of positions at
    a time."""
    B, T, d = x.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    xs = jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                      .reshape(B, n, chunk, d), 1, 0)
    ls = jnp.moveaxis(jnp.pad(labels, ((0, 0), (0, pad)))
                      .reshape(B, n, chunk), 1, 0)
    valid = (jnp.arange(n * chunk) < T).reshape(n, chunk).astype(F32)
    real = jnp.arange(head.shape[-1]) < vocab

    @jax.checkpoint
    def one(args):
        xc, lc, vc = args
        logits = mm("bcd,dv->bcv", xc, head, mode).astype(F32)
        logits = jnp.where(real, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return jnp.sum((lse - gold) * vc[None])

    return jnp.sum(jax.lax.map(one, (xs, ls, valid))) / (B * T)


# ---------------------------------------------------------------- optimizer
def _lr(t, opt: dict):
    warm = opt["lr"] * t / max(opt["warmup_steps"], 1)
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    cos = opt["lr"] * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(t < opt["warmup_steps"], warm, cos)


def _step(params, m, v, t, tokens, loss, opt):
    loss, g = jax.value_and_grad(loss)(params, tokens)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    g = jax.tree.map(lambda x: x * scale, g)
    tf = t.astype(F32)
    b1, b2 = opt["b1"], opt["b2"]
    lr = _lr(tf, opt)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * jnp.square(x), v, g)

    def upd(p, a, b):
        ah, bh = a / (1 - b1 ** tf), b / (1 - b2 ** tf)
        return p - lr * (ah / (jnp.sqrt(bh) + opt["eps"])
                         + opt["weight_decay"] * p)
    params = jax.tree.map(upd, params, m, v)
    return params, m, v, loss, leaf_norms(g)


def step_fn(model, conf: dict, opt: dict, mode: str = "bf16"):
    """One AdamW step of `model`'s loss, to be jitted:
    (params, m, v, t, tokens) -> (params, m, v, loss, gradient norms)."""
    return functools.partial(_step, opt=opt, loss=functools.partial(
        model.loss_fn, conf=conf, mode=mode))


def train(model, conf: dict, opt: dict, key, batches, *, mode: str = "bf16",
          rows: int | None = None) -> dict:
    """The first STEPS steps of `model` from its init_params(key) on
    `batches` (numpy [B, S+1] each; `rows` keeps the first rows only).
    Returns each step's loss, each leaf's norm of the first clipped
    gradient and of the parameters' change over the steps."""
    init = jit_init(model, conf)
    params = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    step = jax.jit(step_fn(model, conf, opt, mode), donate_argnums=(0, 1, 2))
    losses, grad = [], None
    for i, b in enumerate(batches[:STEPS]):
        b = np.asarray(b)[:rows] if rows else np.asarray(b)
        params, m, v, loss, g = step(params, m, v, jnp.int32(i + 1),
                                     jnp.asarray(b))
        losses.append(float(loss))
        grad = np.asarray(g) if grad is None else grad
    del m, v
    change = np.asarray(jax.jit(lambda p, p0: leaf_norms(jax.tree.map(
        jnp.subtract, p, p0)))(params, init(key)))
    return {"loss": np.asarray(losses), "grad": grad, "change": change}


# ------------------------------------------------------------- comparison
def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared, each the worst over steps or leaves:
    ``loss_gap``, |loss - reference| / reference over the steps;
    ``grad_gap`` and ``change_gap``, the gap between a leaf's norm and the
    reference's, over the larger of the reference's norm of that leaf and
    of the median leaf. Leaves whose reference gradient is under a
    thousandth of the median leaf's (moved by round-off alone) are left out
    of the change."""
    def worst(a, b, keep):
        a, b = np.asarray(a, np.float64)[keep], np.asarray(b, np.float64)[keep]
        floor = max(float(np.median(b)), 1e-30)
        return float(np.max(np.abs(a - b) / np.maximum(b, floor)))

    rg = np.asarray(ref["grad"], np.float64)
    moved = rg >= 1e-3 * np.median(rg)
    lr_ = np.asarray(ref["loss"], np.float64)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(got["loss"]) - lr_)
                                 / np.abs(lr_))),
        "grad_gap": worst(got["grad"], ref["grad"], slice(None)),
        "change_gap": worst(got["change"], ref["change"], moved),
    }
