"""``trace_split.py`` on the CPU at a tiny size: a traced run of the
checkpointing cell, split by the program's scopes and spans."""
import pytest

import tiny
import trace_split
from harness import progtrace, spec


@pytest.fixture(scope="module")
def split(tmp_path_factory):
    from repro.launch import compile_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(spec, "peaks", lambda kind: {"bf16_flops": 1e12})
    mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    try:
        cell = tiny.tiny_cell("mamba2-370m", "ckpt", "mamba2-370m.ckpt")
        yield trace_split.split(cell, 2**31 + 91, 1.5,
                                str(tmp_path_factory.mktemp("work")), 0.0)
    finally:
        mp.undo()


def test_the_split_reads_the_program_that_ran(split):
    prog = split["program"]
    assert split["correct"]
    assert prog["hlo_module"] == "jit_train_step"
    assert prog["unmatched_share"] == 0.0
    # the save's checksum runs in the window as a module of its own
    assert set(prog["module_share"]) >= {"jit_train_step", "jit_checksums"}
    assert sum(prog["module_share"].values()) == pytest.approx(1.0)


def test_every_scope_reads_device_time(split):
    """On the CPU the ops of several scopes run at once on the thread
    pool, so the unions overlap and do not sum to the busy time (the
    synthetic split in ``test_bench_window`` checks the sum)."""
    each, metrics = split["program"]["scope_ms"], split["metrics"]
    assert set(each) == set(progtrace.SCOPES) | {"other"}
    assert all(v > 0 for v in each.values()), each
    groups = ("ssd_ms", "proj_ms", "loss_ms", "optimizer_ms", "conv_ms")
    assert all(metrics[g] > 0 for g in groups), metrics
    assert metrics["ssd_ms"] == each["ssd"]
    assert metrics["loss_ms"] == each["loss"]
    assert metrics["starved_steps"] == split["program"]["starved_steps"]


def test_the_writer_phases_and_step_spans_are_read(split):
    prog = split["program"]
    per_save = prog["per_save_s"]
    for name in ("ckpt.write", "ckpt.serialize", "ckpt.file_write",
                 "ckpt.fsync", "ckpt.commit", "ckpt.snapshot"):
        assert per_save.get(name, 0) > 0, name
    assert (per_save["ckpt.serialize"] + per_save["ckpt.fsync"]
            <= per_save["ckpt.write"])
    counts = prog["span_counts"]
    # the window's last batch is refused (one input with no dispatch),
    # and the window may open between a step's input and its dispatch
    assert counts["train.dispatch"] > 0
    assert abs(counts["train.input"] - 1 - counts["train.dispatch"]) <= 1
    assert {"train.input", "train.dispatch"} <= set(prog["per_step_ms"])
    assert isinstance(prog["starved_steps"], int)
    assert prog["starved_steps"] >= 0
