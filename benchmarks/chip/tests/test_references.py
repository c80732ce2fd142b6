"""Each configuration names its architecture's module under references/:
the module is found by that name and has what the harness takes from it, a
file that names none or an unknown one is refused as the cell loads, and
the decoder's initial weights are the bits they were before the module
existed."""
import hashlib
import json

import pytest

import bench
import reference
from references import decoder
from test_correct import GRANITE, QWEN, SEED

# sha256 (first 16 hex digits) of each leaf that the jitted init_params
# makes from PRNGKey(SEED) on the CPU, taken from the single reference.py
# that the decoder module was split from
GOLDEN = {
    "QWEN": {
        "['embed']['head']": "eab8dd2487f3d394",
        "['embed']['tok']": "8bd37b515efb999e",
        "['final_norm']": "2f20cd03c9cd392a",
        "['stack']['b0']['attn']['k_norm']": "b638277a8690e175",
        "['stack']['b0']['attn']['q_norm']": "b638277a8690e175",
        "['stack']['b0']['attn']['wk']": "d7462299a441de24",
        "['stack']['b0']['attn']['wo']": "b643b0a6a033b843",
        "['stack']['b0']['attn']['wq']": "a5332d5cdb893406",
        "['stack']['b0']['attn']['wv']": "b11ef7cb31704f64",
        "['stack']['b0']['ln1']": "02722f124d0f1736",
        "['stack']['b0']['ln2']": "02722f124d0f1736",
        "['stack']['b0']['mlp']['w_down']": "08b49de0b4e095fa",
        "['stack']['b0']['mlp']['w_gate']": "90def2a4845c4f5a",
        "['stack']['b0']['mlp']['w_up']": "4c07da78472a2bdc",
    },
    "GRANITE": {
        "['embed']['tok']": "8d86a601c3092d41",
        "['final_norm']": "2f20cd03c9cd392a",
        "['stack']['b0']['attn']['wk']": "242757477fee879a",
        "['stack']['b0']['attn']['wo']": "2d994d3529020cbd",
        "['stack']['b0']['attn']['wq']": "20f6adcbd827860a",
        "['stack']['b0']['attn']['wv']": "d7462299a441de24",
        "['stack']['b0']['ln1']": "02722f124d0f1736",
        "['stack']['b0']['ln2']": "02722f124d0f1736",
        "['stack']['b0']['moe']['router']": "f0bf69fce9f30cfe",
        "['stack']['b0']['moe']['w_down']": "4e27d2d5d05c7b2b",
        "['stack']['b0']['moe']['w_gate']": "008292835e8372ab",
        "['stack']['b0']['moe']['w_up']": "b997e9424fb5bdc6",
    },
}


@pytest.mark.parametrize("name,conf", [("QWEN", QWEN), ("GRANITE", GRANITE)])
def test_decoder_initial_weights_keep_their_bits(name, conf):
    import jax
    import numpy as np
    params = reference.jit_init(decoder, conf)(jax.random.PRNGKey(SEED))
    got = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest()[:16]
        for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == GOLDEN[name]


# what the harness takes from an architecture's module, and nothing else
INTERFACE = ("program_fields", "as_run_readings", "param_specs",
             "init_params", "loss_fn", "forward_flops_per_token")


@pytest.mark.parametrize("name", [c["name"] for c in
                                  bench.load_spec()["configs"]])
def test_configurations_name_a_module_with_the_interface(name):
    spec = bench.load_spec()
    conf = {c["name"]: c for c in spec["configs"]}[name]
    model = reference.module(json.loads((bench.ROOT / conf["file"])
                                        .read_text()))
    for attr in INTERFACE:
        assert callable(getattr(model, attr, None)), attr


def _spec_with(tmp_path, conf: dict) -> dict:
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(conf))
    spec = bench.load_spec()
    w = dict(spec["workloads"][0], name="w", config="arch-x")
    return {**spec, "workloads": [w], "configs": [
        {"name": "arch-x", "file": str(path)}]}


@pytest.mark.parametrize("ref,says", [
    (None, "'arch-x' names no reference module"),
    ("", "'arch-x' names no reference module"),
    ("mamba_hybrid", "'arch-x' names reference module 'mamba_hybrid'"),
    ("../reference", "'arch-x' names reference module '../reference'"),
])
def test_configuration_without_a_known_module_fails_at_load(tmp_path, ref,
                                                            says):
    conf = {k: v for k, v in QWEN.items() if k != "reference"}
    if ref is not None:
        conf["reference"] = ref
    with pytest.raises(ValueError) as err:
        bench.load_cell(_spec_with(tmp_path, conf), "w")
    assert says in str(err.value)
