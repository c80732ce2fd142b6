"""Plain reference of a decoder with one scanned stack of identical layers.

What a configuration file that names ``"reference": "decoder"`` states, in
straightforward jnp, importing nothing of the program: RMSNorm, rotary
attention with grouped KV heads and optional qk-norm, a SwiGLU MLP or a
top-k mixture of experts with capacity and a load-balancing loss, and the
cross-entropy over the real vocabulary (qwen3-8b, granite-moe-3b-a800m).
The shared pieces (matmul, norms, attention, loss, AdamW, ``train``) are in
``reference.py``; this module holds what is particular to the architecture:

- ``program_fields`` and ``as_run_readings``: the program's config built
  from the file's keys, and what the program says for each ``as_run`` key;
- ``param_specs``, ``init_params``, ``loss_fn``: the weights and the loss;
- ``forward_flops_per_token``: the model FLOPs of a token, from the file.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from reference import (BF16, F32, attention, cross_entropy, init_from_specs,
                       mm, rms, rope, swiglu)


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


# ------------------------------------------------------------- the program
def program_fields(conf: dict) -> dict:
    """The program's ModelConfig fields that the file states; ``name`` is
    the program's architecture whose other settings apply."""
    return {"name": conf["program_arch"],
            "num_layers": conf["num_hidden_layers"],
            "vocab_size": conf["vocab_size"], "d_model": conf["hidden_size"],
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf.get("head_dim", 0),
            "d_ff": conf["intermediate_size"],
            "num_experts": conf.get("num_local_experts", 0),
            "experts_per_token": conf.get("num_experts_per_tok", 0),
            "tie_embeddings": bool(conf["tie_word_embeddings"]),
            "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["rms_norm_eps"])}


def as_run_readings(cfg) -> dict:
    """What the program's config says for each key of the file's
    ``as_run`` block, which this reference follows."""
    said = {"vocab_padded": cfg.padded_vocab, "qk_norm": cfg.qk_norm,
            "embedding_scale": cfg.d_model ** 0.5 if cfg.tie_embeddings
            else 1.0}
    if cfg.num_experts:
        said.update(capacity_factor=cfg.capacity_factor,
                    router_aux_loss_coef=cfg.router_aux_coef)
    return said


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


def init_params(conf: dict, key):
    """Float32 weights from a PRNG key: normal(0, std) or ones."""
    return init_from_specs(param_specs(conf), key)


# -------------------------------------------------------------------- model
def _moe(p, h, m, mode, block=2048):
    """Top-k routing over all tokens of the batch; an expert keeps its
    first `capacity` assignments in (token, k) order and drops the rest.
    Every expert runs on every token, weighted by its kept gate."""
    B, S, d = h.shape
    T, E, K = B * S, m.experts, m.topk
    xt = h.reshape(T, d)
    probs = jax.nn.softmax(mm("td,de->te", xt, p["router"], mode)
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
        up = mm("td,edf->tef", x, p["w_up"], mode)
        gt = jax.nn.silu(mm("td,edf->tef", x, p["w_gate"], mode))
        y = mm("tef,efd->ted", gt * up, p["w_down"], mode)
        return jnp.einsum("ted,te->td", y, wb)

    y = jax.lax.map(one, xs).reshape(nb * block, d)[:T].reshape(B, S, d)
    counts = hit.sum(0).astype(F32)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    return y, E * jnp.sum(frac * probs.mean(0))


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
        h = rms(x, p["ln1"], m.eps)
        a = p["attn"]
        q = mm("bsd,dhk->bshk", h, a["wq"], mode)
        k = mm("bsd,dhk->bshk", h, a["wk"], mode)
        v = mm("bsd,dhk->bshk", h, a["wv"], mode)
        if m.qk_norm:
            q, k = rms(q, a["q_norm"], m.eps), rms(k, a["k_norm"], m.eps)
        q, k = rope(q, m.theta), rope(k, m.theta)
        x = x + mm("bshk,hkd->bsd", attention(q, k, v), a["wo"], mode)
        h = rms(x, p["ln2"], m.eps)
        if m.experts:
            o, aux = _moe(p["moe"], h, m, mode)
        else:
            o, aux = swiglu(p["mlp"], h, mode), jnp.zeros((), F32)
        return x + o, aux

    x, aux = jax.lax.scan(layer, emb, params["stack"]["b0"])
    x = rms(x, params["final_norm"], m.eps)
    head = params["embed"]["tok"].T if m.tied else params["embed"]["head"]
    loss = cross_entropy(x[:, :-1], tokens[:, 1:], head, m.vocab, mode)
    if m.experts:
        loss = loss + m.aux_coef * jnp.sum(aux) / m.layers
    return loss


# -------------------------------------------------------------------- FLOPs
def forward_flops_per_token(conf: dict, seq_len: int) -> float:
    """Forward matmul FLOPs of one token: the attention projections, the
    causal attention scores and values, the SwiGLU MLP or the router and
    the ``num_experts_per_tok`` routed experts only, and the output head
    over the real (unpadded) vocabulary. Not the embedding lookup, which is
    a gather, and not what rematerialisation recomputes."""
    m = dims(conf)
    q, kv = m.h * m.hd, m.kv * m.hd
    proj = 2 * m.d * (q + 2 * kv) + 2 * q * m.d
    # causal: query i attends to i + 1 keys, (S + 1) / 2 on average; one
    # multiply-add for the score and one for the value, per key and width
    core = 2 * 2 * q * (seq_len + 1) / 2
    if m.experts:
        mlp = 2 * m.d * m.experts + m.topk * 3 * 2 * m.d * m.ff
    else:
        mlp = 3 * 2 * m.d * m.ff
    return m.layers * (proj + core + mlp) + 2 * m.d * m.vocab
