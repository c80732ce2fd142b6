"""`correct` on tiny cells on the CPU: sound runs pass; the controls (the
reference in int8 in the program's place, and the program's bf16 codec
path) and each planted fault fail. The harness's look for a chip is
skipped; everything else of a run is driven as on the chip. The tiny cells'
limits are set, like a real cell's, between the readings of sound runs and
of the faults at this size."""
import dataclasses
import time

import pytest

import bench
import control
import reference
import run

QWEN = {"reference": "decoder", "program_arch": "qwen3-8b",
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 500, "rope_theta": 1e6,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "as_run": {"vocab_padded": 512, "qk_norm": True,
                   "embedding_scale": 1.0}}
GRANITE = {"reference": "decoder", "program_arch": "granite-moe-3b-a800m",
           "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "intermediate_size": 32,
           "num_hidden_layers": 2,
           "vocab_size": 515, "num_local_experts": 8, "num_experts_per_tok": 2,
           "rope_theta": 1e4, "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
           "as_run": {"vocab_padded": 768, "qk_norm": False,
                      "embedding_scale": 8.0, "capacity_factor": 1.25,
                      "router_aux_loss_coef": 0.01}}
CELLS = {
    "cycle": {"config_file": QWEN, "traffic_file": {
        "kind": "cycle", "global_batch": 2, "seq_len": 32,
        "steps_per_cycle": 2, "signal_at": [0.1, 0.9]}},
    "async_save": {"config_file": GRANITE, "traffic_file": {
        "kind": "async_save", "global_batch": 2, "seq_len": 32,
        "save_every": 2}},
}
# tiny readings on the CPU, seeds SEED..SEED+2 (loss, grad, change gaps):
# cycle sound <= 2.8e-4, 1.7e-3, 7.6e-4; half >= 1.6e-2, 6.1e-2, 0.25;
# async sound <= 1.8e-3, 6.9e-3, 3.9e-3; half >= 1.0e-2, 0.17, 1.9e-2.
# The int8 control reads 1-8x sound at this size and sets no limit here.
LIMITS = {"cycle": {"loss_gap": 1e-3, "grad_gap": 6e-3, "change_gap": 3e-3},
          "async_save": {"loss_gap": 6e-3, "grad_gap": 0.03,
                         "change_gap": 0.012}}
SEED = 2**31 + 977


def tiny_cell(kind):
    return {"name": f"tiny.{kind}", "chips": 1, "limits": LIMITS[kind],
            **CELLS[kind]}


def tiny_run(kind, codec=None):
    res = run.run_cell(tiny_cell(kind), seed=SEED, seconds=1.0, trace=False,
                       chips=1, t_start=time.perf_counter(), codec=codec)
    return res, {k: c["value"] for k, c in res["checks"].items()}


@pytest.mark.parametrize("kind", ["cycle", "async_save"])
def test_sound_run_is_correct(kind):
    res, checks = tiny_run(kind)
    assert run.is_correct(res), checks
    assert res["attempted"] >= 2


@pytest.mark.parametrize("kind", ["cycle", "async_save"])
def test_readings_sound_under_and_half_batch_over_the_limits(kind):
    r = control.readings(tiny_cell(kind), SEED, faults=True)
    lim = LIMITS[kind]
    assert all(v <= lim[k] for k, v in r["sound"].items()), r
    assert any(v > lim[k] for k, v in r["half"].items()), r
    assert set(r["int8"]) == set(lim)


@pytest.mark.parametrize("kind", ["cycle", "async_save"])
def test_codec_control_is_not_correct(kind):
    res, checks = tiny_run(kind, codec=control.CODEC)
    assert not run.is_correct(res)
    assert checks["leaf_mismatch"] > 0


def _alter_first_float(tree):
    import jax
    import numpy as np
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for i, x in enumerate(leaves):
        if np.asarray(x).dtype == np.float32:
            leaves[i] = np.array(x, copy=True)
            leaves[i].flat[0] += 1.0
            break
    return jax.tree_util.tree_unflatten(treedef, leaves)


def test_restored_answer_altered_fails(monkeypatch):
    from repro.api import CheckpointSession
    orig = CheckpointSession.restore

    def restore(self, request=None):
        res = orig(self, request)
        return dataclasses.replace(res, state=_alter_first_float(res.state))
    monkeypatch.setattr(CheckpointSession, "restore", restore)
    res, checks = tiny_run("cycle")
    assert not run.is_correct(res) and checks["leaf_mismatch"] > 0


def test_cursor_off_by_one_fails(monkeypatch):
    from repro.api.requests import RestoreResult
    orig = RestoreResult.make_iterator

    def make_iterator(self, ds, **kw):
        it = orig(self, ds, **kw)
        it.step += 1
        return it
    monkeypatch.setattr(RestoreResult, "make_iterator", make_iterator)
    for kind in ("cycle", "async_save"):
        res, checks = tiny_run(kind)
        assert not run.is_correct(res), (kind, checks)


def test_captured_answer_altered_fails(monkeypatch):
    from repro.core.async_engine import AsyncCheckpointer
    orig = AsyncCheckpointer.dump_async

    def dump_async(self, tree, **kw):
        return orig(self, _alter_first_float(tree), **kw)
    monkeypatch.setattr(AsyncCheckpointer, "dump_async", dump_async)
    res, checks = tiny_run("async_save")
    assert not run.is_correct(res) and checks["leaf_mismatch"] > 0


@pytest.mark.parametrize("kind", ["cycle", "async_save"])
def test_step_returning_its_state_unchanged_fails(monkeypatch, kind):
    import jax.numpy as jnp

    def compile_step(lm, opt_cfg, state_struct, batch_struct):
        return lambda state, batch: (state, {"loss": jnp.zeros(())})
    monkeypatch.setattr(bench, "compile_step", compile_step)
    res, checks = tiny_run(kind)
    assert not run.is_correct(res)
    assert checks["leaves_unmoved"] > 0
    assert checks["grad_gap"] == checks["change_gap"] == 1.0


@pytest.mark.parametrize("kind", ["cycle", "async_save"])
def test_step_on_half_the_batch_fails(monkeypatch, kind):
    import jax
    orig = bench.compile_step

    def compile_step(lm, opt_cfg, state_struct, batch_struct):
        b = batch_struct["tokens"]
        half = jax.ShapeDtypeStruct((b.shape[0] // 2,) + b.shape[1:], b.dtype,
                                    sharding=b.sharding)
        step = orig(lm, opt_cfg, state_struct, {"tokens": half})
        return lambda state, batch: step(
            state, {"tokens": batch["tokens"][:half.shape[0]]})
    monkeypatch.setattr(bench, "compile_step", compile_step)
    res, checks = tiny_run(kind)
    assert not run.is_correct(res), checks
    assert checks["batch_mismatch"] == 0          # fed right, half used
    assert any(checks[k] > v for k, v in LIMITS[kind].items()), checks


def test_reference_weight_tree_matches_the_program():
    import jax
    from repro.models.model import LM
    for conf in (QWEN, GRANITE):
        model = reference.module(conf)
        got = jax.eval_shape(lambda c=conf: model.init_params(
            c, jax.random.PRNGKey(0)))
        assert got == LM(bench.model_config(conf, model)).abstract()
