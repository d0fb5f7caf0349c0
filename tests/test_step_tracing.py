"""The training step's own tracing: the Trainer's step annotation and
``train.*`` spans, the profiler's ``profiler.*`` spans, the checkpoint
writer's ``ckpt.*`` spans, all in a ``jax.profiler`` trace, and the
``train.starved_dispatches`` counter."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.train.trainer import Trainer, TrainerConfig

SEQ, BATCH = 64, 2


def _batches(vocab, delay_s=0.0):
    rng = np.random.default_rng(0)
    while True:
        if delay_s:
            time.sleep(delay_s)
        yield rng.integers(0, vocab, (BATCH, SEQ)).astype(np.int32)


def _threads(trace_dir):
    """Each host thread's events, as (name, start_ns, end_ns) lists."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.append([(e.name, e.start_ns, e.end_ns)
                            for e in line.events])
    return out


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outers):
    return all(any(o[1] <= i[1] and i[2] <= o[2] for o in outers)
               for i in inner)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Three steps, an async save after step 2 and the final sync save
    after step 3, the profiler restarting every step, all traced."""
    tmp = tmp_path_factory.mktemp("traced")
    cfg = get_config("mamba2-370m", reduced=True)
    tcfg = TrainerConfig(steps=3, checkpoint_every=2, log_every=100,
                         checkpoint_dir=str(tmp / "ck"), profile_first=0,
                         profile_last=2, profile_every=1)
    trainer = Trainer(cfg, tcfg, _batches(cfg.vocab_size))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp / "trace"), profiler_options=opts):
        out = trainer.run()
    assert out["final_step"] == 3
    return _threads(str(tmp / "trace"))


def test_each_step_is_annotated_with_its_host_phases(traced_run):
    main = next(t for t in traced_run if _named(t, "train.dispatch"))
    steps = _named(main, "train")
    assert len(steps) == 3
    for name in ("train.input", "train.to_device", "train.dispatch"):
        spans = _named(main, name)
        assert len(spans) == 3, name
        assert _inside(spans, steps), name
    saves = _named(main, "train.save")
    assert len(saves) == 2 and _inside(saves, steps)
    assert _inside(_named(main, "ckpt.snapshot"), saves)
    assert _named(main, "ckpt.snapshot")


def test_profiler_restarts_show_as_stop_and_start(traced_run):
    main = next(t for t in traced_run if _named(t, "train.dispatch"))
    # start at step 0, restarts at steps 1 and 2, stop after step 2
    assert len(_named(main, "profiler.start")) == 3
    assert len(_named(main, "profiler.stop")) == 3
    assert _inside(_named(main, "profiler.start") + _named(main, "profiler.stop"),
                   _named(main, "train"))


def test_the_writer_thread_nests_its_phases_in_ckpt_write(traced_run):
    writers = [t for t in traced_run
               if _named(t, "ckpt.write") and not _named(t, "train")]
    assert len(writers) == 1
    writer = writers[0]
    writes = _named(writer, "ckpt.write")
    assert len(writes) == 1               # the async save of step 2
    for name in ("ckpt.serialize", "ckpt.file_write", "ckpt.fsync",
                 "ckpt.commit"):
        assert _named(writer, name), name
        assert _inside(_named(writer, name), writes), name
    # one serialize per leaf; one write and fsync per leaf and MANIFEST
    n_leaves = len(_named(writer, "ckpt.serialize"))
    assert len(_named(writer, "ckpt.fsync")) == n_leaves + 1
    assert _inside(_named(writer, "ckpt.fsync")[-1:],
                   _named(writer, "ckpt.commit"))


def test_a_slow_feed_starves_every_dispatch_after_the_first(tmp_path):
    cfg = get_config("mamba2-370m", reduced=True)
    tcfg = TrainerConfig(steps=4, checkpoint_every=100, log_every=100,
                         checkpoint_dir=str(tmp_path / "ck"))
    trainer = Trainer(cfg, tcfg, _batches(cfg.vocab_size, delay_s=1.0))
    out = trainer.run()
    assert out["telemetry"]["counters"]["train.starved_dispatches"] == 3
