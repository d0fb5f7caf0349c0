"""A whole run of the harness on the CPU at a tiny size (the look for a
chip skipped): sound, it is correct; with the timed path broken
underneath, it is not.  And the control, the reference a precision step
lower, fails the comparison."""
import json
import os

import jax
import numpy as np
import pytest

import tiny
from harness import cell as cell_lib, check, spec, traffic
from reference.common import Dot


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops": 1e12})
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")

    def go(config="mamba2-370m", mix="steady", trace=False):
        cell = tiny.tiny_cell(config, mix, f"{config}.{mix}")
        return cell_lib.run_cell(cell, 2**31 + 77, 0.5, trace,
                                 str(tmp_path / "work"), 0.0)
    return go


def failing(outcome):
    return {n.name for n in outcome.numbers if not n.ok}


@pytest.mark.parametrize("mix", ["steady", "ckpt", "bare"])
def test_a_sound_run_is_correct(cpu_run, mix):
    config = "mamba2-370m"
    out = cpu_run(config, mix)
    assert not failing(out), check.format_numbers(out.numbers)
    assert out.window_compiles == 0
    assert len(out.run.window_steps) > 0
    names = {n.name for n in out.numbers}
    assert ("ckpt_leaves_mismatched" in names) == (mix == "ckpt")
    assert ("posix_record_gap" in names) == (mix != "bare")
    for m in tiny.tiny_cell(config, mix).metrics:
        if m.kind == "end_to_end":
            value = spec.reader(m.name)(out.run)
            assert value is not None and value > 0, m.name


def _broken_step(monkeypatch, fault):
    from repro.train import trainer as trainer_mod
    make = trainer_mod.make_train_step

    def make_broken(cfg, ocfg, microbatches=1):
        step = make(cfg, ocfg, microbatches=microbatches)
        if fault == "state_unchanged":
            return lambda p, o, b: (p, o, step(p, o, b)[2])
        return lambda p, o, b: step(
            p, o, {"tokens": b["tokens"][:b["tokens"].shape[0] // 2]})
    monkeypatch.setattr(trainer_mod, "make_train_step", make_broken)


def _altered_token(monkeypatch):
    from repro.data import tokens as tokens_mod
    batches = tokens_mod.token_batches

    def altered(*a, **k):
        for i, b in enumerate(batches(*a, **k)):
            if i == 1:
                b = b.copy()
                b[0, 5] = (b[0, 5] + 1) % a[3]
            yield b
    monkeypatch.setattr(tokens_mod, "token_batches", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(cpu_run, monkeypatch, fault):
    if fault == "token_altered":
        _altered_token(monkeypatch)
    else:
        _broken_step(monkeypatch, fault)
    bad = failing(cpu_run())
    assert bad, fault
    if fault == "token_altered":
        assert "batches_mismatched" in bad
    else:
        assert bad & {"grad_norm_gap", "grad_diff_gap", "leaf_change_gap"}


def test_the_lower_precision_control_fails_the_comparison():
    cell = tiny.tiny_cell()
    model, opt = cell.config["model"], cell.config["optimizer"]
    ref = spec.reference(cell.config)
    corpus = traffic.make_corpus(cell.traffic, model["vocab_size"], 5)
    first = []
    for tokens, _ in traffic.expected_batches(
            corpus, cell.traffic["batch"], cell.traffic["seq"],
            model["vocab_size"]):
        first.append(tokens)
        if len(first) == check.REF_STEPS:
            break
    key = cell_lib.seed_key(5)
    base = check.reference_readings(ref, model, opt, key, first)
    control = check.reference_readings(ref, model, opt, key, first, "fp8")
    nums = check.training_numbers(control, base, cell.config["limits"])
    assert any(not n.ok for n in nums), check.format_numbers(nums)
    same = check.training_numbers(base, base, cell.config["limits"])
    assert all(n.ok and n.value == 0 for n in same)


@pytest.mark.parametrize("reading", ["control_fp8", "fault_half_batch"])
def test_readings_report_the_control_and_the_fault_as_not_correct(reading):
    import readings
    line = next(x for x in readings.readings(tiny.tiny_cell(), 2**31 + 5)
                if x["reading"] == reading)
    assert line["correct"] is False
    assert any(not n["ok"] for n in line["numbers"].values())


def test_the_benchmark_weights_fit_the_programs_tree():
    from repro.models import init_params
    cell = tiny.tiny_cell()
    model = cell.config["model"]
    ours = jax.eval_shape(lambda k: spec.reference(cell.config).init(model, k),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: init_params(cell_lib.model_config(model), k),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_a_corrupted_checkpoint_leaf_is_found(tmp_path):
    from repro.train.checkpoint import CheckpointManager
    tree = {"params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
            "opt": {"step": np.int32(3)}}
    CheckpointManager(str(tmp_path)).save(7, tree)
    rec = {"step": 7, "checksums": jax.jit(check.checksums)(tree)}
    assert cell_lib.checkpoint_mismatches(str(tmp_path), [rec]) == 0
    leaf = os.path.join(tmp_path, "step_0000000007", "params.w.npy")
    np.save(leaf, np.ones((3, 4), np.float32))
    assert cell_lib.checkpoint_mismatches(str(tmp_path), [rec]) == 1
    os.remove(os.path.join(tmp_path, "step_0000000007", "MANIFEST.json"))
    assert cell_lib.checkpoint_mismatches(str(tmp_path), [rec]) == 2


def test_the_fp8_dot_rounds_and_the_f32_dot_does_not():
    a = np.linspace(-3, 3, 64, dtype=np.float32).reshape(8, 8)
    exact = a @ a
    assert np.allclose(Dot("f32")("ij,jk->ik", a, a), exact, atol=1e-5)
    assert not np.allclose(Dot("fp8")("ij,jk->ik", a, a), exact, atol=1e-2)
    with pytest.raises(ValueError):
        Dot("int4")


def test_configs_state_their_cut():
    for c in spec.load_benchmark()["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert {"grad_norm_gap", "grad_diff_gap", "leaf_change_gap"} <= set(
            cfg["limits"]) <= {"loss_gap", "grad_norm_gap", "grad_diff_gap",
                               "leaf_change_gap"}
