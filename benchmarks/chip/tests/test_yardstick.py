"""The model-FLOP count against hand counts, and the peaks table."""
from pathlib import Path

import pytest

import bench
import flops
import peaks
from references import decoder


def conf(arch, layers):
    """The configuration file of `arch` with `layers` layers."""
    import json
    name = {"qwen3-8b": "qwen3-8b-1l", "granite-moe-3b-a800m":
            "granite-moe-3b-2l"}[arch]
    path = Path(bench.HERE) / "configs" / f"{name}.json"
    return dict(json.loads(path.read_text()), num_hidden_layers=layers)


def hand(L, d, H, KV, hd, ff, V, S, E=0, K=0):
    """Forward FLOPs per token written out term by term."""
    q, kv = H * hd, KV * hd
    attn = 2 * d * q + 2 * d * kv + 2 * d * kv + 2 * q * d
    core = 2 * q * (S + 1) / 2 + 2 * q * (S + 1) / 2     # QK^T, PV; causal
    mlp = (2 * d * E + K * 3 * 2 * d * ff) if E else 3 * 2 * d * ff
    return L * (attn + core + mlp) + 2 * d * V


QWEN = dict(d=4096, H=32, KV=8, hd=128, ff=12288, V=18992)
GRANITE = dict(d=1536, H=24, KV=8, hd=64, ff=512, V=49155, E=40, K=8)


@pytest.mark.parametrize("arch,layers,vocab,seq,want", [
    ("qwen3-8b", 2, 18992, 1024, hand(2, S=1024, **QWEN)),
    ("qwen3-8b", 1, 18992, 4096, hand(1, S=4096, **QWEN)),
    ("granite-moe-3b-a800m", 6, 49155, 4096, hand(6, S=4096, **GRANITE)),
    ("granite-moe-3b-a800m", 2, 49155, 4096, hand(2, S=4096, **GRANITE)),
])
def test_train_flops_match_hand_count(arch, layers, vocab, seq, want):
    c = dict(conf(arch, layers), vocab_size=vocab)
    assert flops.train_flops_per_token(decoder, c, seq) == pytest.approx(
        3 * want, rel=1e-12)


def test_hand_counts_in_numbers():
    # qwen3-8b-2l at 1024: per layer 83,886,080 projection + 8,396,800
    # attention + 301,989,888 MLP; head 155,582,464
    assert hand(2, S=1024, **QWEN) == 2 * (83886080 + 8396800 + 301989888) \
        + 155582464
    # granite-moe-3b-6l at 4096: per layer 12,582,912 projection +
    # 12,585,984 attention + 122,880 router + 37,748,736 routed experts;
    # tied head 151,004,160
    assert hand(6, S=4096, **GRANITE) == 6 * (12582912 + 12585984 + 122880
                                              + 37748736) + 151004160


def test_routed_experts_only_and_no_embedding_lookup():
    c = conf("granite-moe-3b-a800m", 1)
    more = dict(c, num_local_experts=80)   # unrouted experts add only router
    assert decoder.forward_flops_per_token(more, 8) \
        - decoder.forward_flops_per_token(c, 8) == 2 * 1536 * 40


def test_peaks_known_and_unknown():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")


@pytest.mark.parametrize("name", ["qwen3-8b-1l", "granite-moe-3b-2l"])
def test_count_matches_the_count_from_the_program_config(name):
    """The module's count from the file's keys equals the same formula
    over the program's config built from that file (what step_mfu counted
    before the count moved into the module)."""
    import json
    c = json.loads((Path(bench.HERE) / "configs" / f"{name}.json")
                   .read_text())
    cfg = bench.model_config(c, decoder)
    assert cfg.glu
    d, hd = cfg.d_model, cfg.resolved_head_dim
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    E, K = cfg.num_experts, cfg.experts_per_token
    mlp = (2 * d * E + K * 3 * 2 * d * cfg.d_ff) if E else 3 * 2 * d * cfg.d_ff
    want = cfg.num_layers * (2 * d * (q + 2 * kv) + 2 * q * d
                             + 2 * q * 4097 + mlp) + 2 * d * cfg.vocab_size
    assert decoder.forward_flops_per_token(c, 4096) == want
