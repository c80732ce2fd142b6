"""Plain reference of the training step the benchmark drives.

A straightforward jnp implementation of what a configuration file under
``configs/`` states, written apart from the program and importing nothing
of it: the decoder (RMSNorm, rotary attention with grouped KV heads and
optional qk-norm, a SwiGLU MLP or a top-k mixture of experts with capacity
and a load-balancing loss), the cross-entropy over the real vocabulary,
global-norm clipping, warmup-cosine learning rate and AdamW. Float32
weights and optimizer state, bfloat16 matmul operands with float32
accumulation, as the file's ``state`` says.

The benchmark makes the job's initial weights with ``init_params`` from the
seed, so the reference starts from the same weights without taking them
from the program. ``train`` runs the first steps and returns what
``gaps`` compares with the program's readings of the same steps: each
step's loss, each leaf's norm of the first (clipped) gradient, and each
leaf's norm of the parameters' change over the steps.

``mode="int8"`` is the control: every weight matmul with both operands
rounded to int8 (one scale per tensor), the step below bfloat16.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32
STEPS = 3


def dims(conf: dict) -> SimpleNamespace:
    run = conf["as_run"]
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return SimpleNamespace(
        d=d, h=h, kv=conf["num_key_value_heads"],
        hd=conf.get("head_dim", d // h), ff=conf["intermediate_size"],
        layers=conf["num_hidden_layers"], vocab=conf["vocab_size"],
        vpad=run["vocab_padded"], tied=bool(conf["tie_word_embeddings"]),
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        qk_norm=bool(run["qk_norm"]), embed_scale=float(run["embedding_scale"]),
        experts=conf.get("num_local_experts", 0),
        topk=conf.get("num_experts_per_tok", 0),
        capacity=float(run.get("capacity_factor", 0.0)),
        aux_coef=float(run.get("router_aux_loss_coef", 0.0)))


# ------------------------------------------------------------------ weights
def param_specs(conf: dict) -> dict:
    """The weight tree: each leaf (shape, init std), std None for ones."""
    m = dims(conf)
    L, d, ff = m.layers, m.d, m.ff
    attn = {"wq": ((L, d, m.h, m.hd), d ** -0.5),
            "wk": ((L, d, m.kv, m.hd), d ** -0.5),
            "wv": ((L, d, m.kv, m.hd), d ** -0.5),
            "wo": ((L, m.h, m.hd, d), (m.h * m.hd) ** -0.5)}
    if m.qk_norm:
        attn["q_norm"] = ((L, m.hd), None)
        attn["k_norm"] = ((L, m.hd), None)
    block = {"ln1": ((L, d), None), "ln2": ((L, d), None), "attn": attn}
    if m.experts:
        E = m.experts
        block["moe"] = {"router": ((L, d, E), d ** -0.5),
                        "w_up": ((L, E, d, ff), d ** -0.5),
                        "w_gate": ((L, E, d, ff), d ** -0.5),
                        "w_down": ((L, E, ff, d), ff ** -0.5)}
    else:
        block["mlp"] = {"w_up": ((L, d, ff), d ** -0.5),
                        "w_gate": ((L, d, ff), d ** -0.5),
                        "w_down": ((L, ff, d), ff ** -0.5)}
    embed = {"tok": ((m.vpad, d), d ** -0.5)}
    if not m.tied:
        embed["head"] = ((d, m.vpad), d ** -0.5)
    return {"embed": embed, "final_norm": ((d,), None),
            "stack": {"b0": block}}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(conf: dict, key):
    """Float32 weights from a PRNG key: normal(0, std) or ones."""
    specs = param_specs(conf)
    flat, tree = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    out = []
    for i, (shape, std) in enumerate(flat):
        if std is None:
            out.append(jnp.ones(shape, F32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         F32) * std)
    return jax.tree_util.tree_unflatten(tree, out)


def jit_init(conf: dict):
    """init_params compiled for `conf`: the one program that makes the
    initial weights, for the job and for the reference alike."""
    return jax.jit(functools.partial(init_params, conf))


def leaf_norms(tree):
    """Float32 L2 norm of each leaf, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


# -------------------------------------------------------------------- model
def _int8(x):
    """Round to int8 with one scale per tensor; the gradient passes through."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(F32))), 1e-30) / 127.0
    q = (jnp.clip(jnp.round(x.astype(F32) / s), -127, 127) * s).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, w, mode):
    a, w = a.astype(BF16), w.astype(BF16)
    if mode == "int8":
        a, w = _int8(a), _int8(w)
    return jnp.einsum(eq, a, w)


def _rms(x, scale, eps):
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * scale.astype(F32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate the two halves of each head by position x frequency."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, block=256):
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


def _mlp(p, h, mode):
    up = _mm("bsd,df->bsf", h, p["w_up"], mode)
    gate = jax.nn.silu(_mm("bsd,df->bsf", h, p["w_gate"], mode))
    return _mm("bsf,fd->bsd", gate * up, p["w_down"], mode)


def _moe(p, h, m, mode, block=2048):
    """Top-k routing over all tokens of the batch; an expert keeps its
    first `capacity` assignments in (token, k) order and drops the rest.
    Every expert runs on every token, weighted by its kept gate."""
    B, S, d = h.shape
    T, E, K = B * S, m.experts, m.topk
    xt = h.reshape(T, d)
    probs = jax.nn.softmax(_mm("td,de->te", xt, p["router"], mode)
                           .astype(F32), -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    cap = max(8, int(T * K * m.capacity // E))
    hit = (idx.reshape(T * K)[:, None] == jnp.arange(E)).astype(jnp.int32)
    ahead = ((jnp.cumsum(hit, 0) - hit) * hit).sum(-1)   # place in its expert
    kept = (gate.reshape(T * K) * (ahead < cap)).astype(BF16)
    w = (hit.astype(BF16) * kept[:, None]).reshape(T, K, E).sum(1)   # [T, E]
    nb = -(-T // block)
    pad = ((0, nb * block - T), (0, 0))
    xs = (jnp.pad(xt, pad).reshape(nb, block, d),
          jnp.pad(w, pad).reshape(nb, block, E))

    @jax.checkpoint
    def one(args):
        x, wb = args
        up = _mm("td,edf->tef", x, p["w_up"], mode)
        gt = jax.nn.silu(_mm("td,edf->tef", x, p["w_gate"], mode))
        y = _mm("tef,efd->ted", gt * up, p["w_down"], mode)
        return jnp.einsum("ted,te->td", y, wb)

    y = jax.lax.map(one, xs).reshape(nb * block, d)[:T].reshape(B, S, d)
    counts = hit.sum(0).astype(F32)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    return y, E * jnp.sum(frac * probs.mean(0))


def _cross_entropy(x, labels, head, m, mode, chunk=512):
    """Mean next-token loss; logits over the padded vocabulary with the
    padding masked, one chunk of positions at a time."""
    B, T, d = x.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    xs = jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                      .reshape(B, n, chunk, d), 1, 0)
    ls = jnp.moveaxis(jnp.pad(labels, ((0, 0), (0, pad)))
                      .reshape(B, n, chunk), 1, 0)
    valid = (jnp.arange(n * chunk) < T).reshape(n, chunk).astype(F32)
    real = jnp.arange(m.vpad) < m.vocab

    @jax.checkpoint
    def one(args):
        xc, lc, vc = args
        logits = _mm("bcd,dv->bcv", xc, head, mode).astype(F32)
        logits = jnp.where(real, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return jnp.sum((lse - gold) * vc[None])

    return jnp.sum(jax.lax.map(one, (xs, ls, valid))) / (B * T)


def loss_fn(params, tokens, conf: dict, mode: str = "bf16"):
    """tokens [B, S+1]: the model reads tokens[:, :S+1] and predicts
    tokens[:, 1:]; the loss adds the mean load-balancing term of the
    expert layers."""
    m = dims(conf)
    emb = jnp.take(params["embed"]["tok"].astype(BF16), tokens, axis=0)
    if m.embed_scale != 1.0:
        emb = emb * jnp.asarray(m.embed_scale, BF16)

    @jax.checkpoint
    def layer(x, p):
        h = _rms(x, p["ln1"], m.eps)
        a = p["attn"]
        q = _mm("bsd,dhk->bshk", h, a["wq"], mode)
        k = _mm("bsd,dhk->bshk", h, a["wk"], mode)
        v = _mm("bsd,dhk->bshk", h, a["wv"], mode)
        if m.qk_norm:
            q, k = _rms(q, a["q_norm"], m.eps), _rms(k, a["k_norm"], m.eps)
        q, k = _rope(q, m.theta), _rope(k, m.theta)
        x = x + _mm("bshk,hkd->bsd", _attention(q, k, v), a["wo"], mode)
        h = _rms(x, p["ln2"], m.eps)
        if m.experts:
            o, aux = _moe(p["moe"], h, m, mode)
        else:
            o, aux = _mlp(p["mlp"], h, mode), jnp.zeros((), F32)
        return x + o, aux

    x, aux = jax.lax.scan(layer, emb, params["stack"]["b0"])
    x = _rms(x, params["final_norm"], m.eps)
    head = params["embed"]["tok"].T if m.tied else params["embed"]["head"]
    loss = _cross_entropy(x[:, :-1], tokens[:, 1:], head, m, mode)
    if m.experts:
        loss = loss + m.aux_coef * jnp.sum(aux) / m.layers
    return loss


# ---------------------------------------------------------------- optimizer
def _lr(t, opt: dict):
    warm = opt["lr"] * t / max(opt["warmup_steps"], 1)
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    cos = opt["lr"] * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(t < opt["warmup_steps"], warm, cos)


def _step(params, m, v, t, tokens, conf, opt, mode):
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, conf, mode)
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


def train(conf: dict, opt: dict, key, batches, *, mode: str = "bf16",
          rows: int | None = None) -> dict:
    """The first STEPS steps from init_params(key) on `batches` (numpy
    [B, S+1] each; `rows` keeps the first rows only). Returns each step's
    loss, each leaf's norm of the first clipped gradient and of the
    parameters' change over the steps."""
    init = jit_init(conf)
    params = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    step = jax.jit(functools.partial(_step, conf=conf, opt=opt, mode=mode),
                   donate_argnums=(0, 1, 2))
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

