"""repro.tune unit surface: actions wire codec, policies, controller
(cooldown / dry-run / one-way degradation), applier (idempotency,
migration, thread resize via PipelineControl, checkpoint throttle),
registry integration, options validation, and the local closed loop
through the Profiler façade."""
import os
import time

import pytest

from repro.insight.detectors import Finding
from repro.link import WireError
from repro.link.messages import decode, encode
from repro.tune import (ACTION_KINDS, TUNE_VERSION, LocalTuneLoop,
                        TuneAck, TuneAction, TuneApplier, TuneController,
                        current_applier, make_builtin_policy,
                        set_current_applier)
from repro.tune.actions import (decode_acks, decode_actions,
                                encode_actions, encode_poll)


def finding(detector="small-file-storm", rank=None, severity=0.8):
    return Finding(detector=detector, title=detector, severity=severity,
                   window=(0.0, 1.0), evidence={}, recommendation="",
                   rank=rank)


def make_controller(dry_run=False, cooldown_s=0.0, policies=None):
    if policies is None:
        policies = [make_builtin_policy("stage-hot-files")]
    return TuneController(policies, dry_run=dry_run, cooldown_s=cooldown_s)


# ---------------------------------------------------------------- actions
class TestActionWire:
    def test_round_trip(self):
        a = TuneAction(action_id="a0001", kind="migrate-file",
                       params={"tier": "optane"}, policy="stage-hot-files",
                       reason="storm", rank=2, issued_at=1.5)
        b = TuneAction.from_dict(a.to_dict())
        assert b == a
        assert b.v == TUNE_VERSION

    def test_unknown_kind_rejected(self):
        with pytest.raises(WireError):
            TuneAction.from_dict({"action_id": "x", "kind": "reboot",
                                  "params": {}, "v": TUNE_VERSION})

    def test_newer_version_rejected(self):
        d = TuneAction(action_id="a1", kind="resize-threads",
                       params={}).to_dict()
        d["v"] = TUNE_VERSION + 1
        with pytest.raises(WireError):
            TuneAction.from_dict(d)

    def test_ack_round_trip(self):
        ack = TuneAck("a1", 3, "applied", before={"threads": 4},
                      after={"threads": 8}, detail="ok")
        assert TuneAck.from_dict(ack.to_dict()) == ack

    def test_poll_and_actions_messages(self):
        line = encode_poll(1, [TuneAck("a1", 1, "applied").to_dict()])
        msg = decode(line)
        assert msg.kind == "tune" and msg.payload["poll"]
        acks = decode_acks(msg.payload)
        assert acks[0].action_id == "a1"
        reply = encode_actions(
            1, [TuneAction(action_id="a2", kind="resize-threads",
                           params={"direction": "up"})], dry_run=True)
        actions = decode_actions(reply.payload)
        assert actions[0].kind == "resize-threads"
        assert reply.payload["dry_run"] is True

    def test_tune_verb_registered(self):
        # the verb rides the shared plugin registry like any extension
        from repro.profiler import registry
        assert "tune" in registry.get_registry("verb")
        # the codec accepts the kind end to end
        decode(encode("tune", 0, {"poll": True, "acks": []}))


# ---------------------------------------------------------------- policies
class TestPolicies:
    def test_stage_hot_files_plans_migration(self):
        actions = make_builtin_policy("stage-hot-files").plan(
            finding("small-file-storm", rank=1))
        assert len(actions) == 1
        a = actions[0]
        assert a.kind == "migrate-file" and a.rank == 1
        assert a.params["tier"] == "optane"

    def test_autotune_threads_direction(self):
        pol = make_builtin_policy("autotune-threads")
        up = pol.plan(finding("small-file-storm"))
        assert up[0].params["direction"] == "up"
        down = pol.plan(finding("straggler-read-tail"))
        assert down[0].params["direction"] == "down"

    def test_checkpoint_backoff_scales_with_severity(self):
        pol = make_builtin_policy("checkpoint-backoff")
        low = pol.plan(finding("checkpoint-stall", severity=0.3))
        high = pol.plan(finding("checkpoint-stall", severity=1.0))
        assert high[0].params["min_interval_s"] \
            > low[0].params["min_interval_s"]

    def test_unrelated_finding_plans_nothing(self):
        for name in ("stage-hot-files", "autotune-threads",
                     "checkpoint-backoff"):
            assert make_builtin_policy(name).plan(
                finding("random-read-thrash")) == []

    def test_unknown_policy_name(self):
        with pytest.raises(ValueError):
            make_builtin_policy("defragment-the-moon")

    def test_registry_create(self):
        from repro.profiler import registry
        pol = registry.create("policy", "stage-hot-files", None)
        assert pol.plan(finding())[0].kind == "migrate-file"


# -------------------------------------------------------------- controller
class TestController:
    def test_plan_issue_ack_lifecycle(self):
        ctrl = make_controller()
        planned = ctrl.on_findings([finding(rank=0)])
        assert len(planned) == 1
        assert ctrl.entries[0].status == "planned"
        actions = ctrl.poll_actions(0)
        assert [a.action_id for a in actions] == [planned[0].action_id]
        assert ctrl.entries[0].status == "issued"
        assert ctrl.record_ack(TuneAck(planned[0].action_id, 0, "applied"))
        assert ctrl.entries[0].status == "acked"
        assert ctrl.poll_actions(0) == []      # acked: no redelivery

    def test_redelivers_until_acked(self):
        ctrl = make_controller()
        ctrl.on_findings([finding(rank=0)])
        first = ctrl.poll_actions(0)
        again = ctrl.poll_actions(0)           # lost reply heals
        assert [a.action_id for a in first] \
            == [a.action_id for a in again]

    def test_targeted_delivery(self):
        ctrl = make_controller()
        ctrl.on_findings([finding(rank=1)])
        assert ctrl.poll_actions(0) == []      # targeted at rank 1
        assert len(ctrl.poll_actions(1)) == 1

    def test_duplicate_acks_counted_once(self):
        ctrl = make_controller()
        aid = ctrl.on_findings([finding(rank=0)])[0].action_id
        ctrl.poll_actions(0)
        assert ctrl.record_ack(TuneAck(aid, 0, "applied"))
        assert not ctrl.record_ack(TuneAck(aid, 0, "applied"))
        assert ctrl.stats["duplicate_acks"] == 1
        assert ctrl.stats["acked"] == 1

    def test_cooldown_suppresses_repeat_plans(self):
        ctrl = make_controller(cooldown_s=60.0)
        assert len(ctrl.on_findings([finding(rank=0)])) == 1
        assert ctrl.on_findings([finding(rank=0)]) == []
        assert ctrl.stats["cooldown_suppressed"] == 1

    def test_one_way_self_acks_dry_run(self):
        ctrl = make_controller()
        ctrl.mark_one_way()
        ctrl.on_findings([finding(rank=0)])
        entry = ctrl.entries[0]
        assert entry.status == "acked" and entry.dry_run
        assert entry.acks[0].status == "dry-run"
        assert "one-way" in entry.acks[0].detail
        assert ctrl.poll_actions(0) == []      # nothing deliverable

    def test_handle_poll_round_trip(self):
        ctrl = make_controller(dry_run=True)
        ctrl.on_findings([finding(rank=0)])
        msg = decode(encode_poll(0, []))
        reply = ctrl.handle_poll(msg)
        assert reply.payload["dry_run"] is True
        assert len(reply.payload["actions"]) == 1

    def test_broken_policy_is_contained(self):
        class Boom:
            name = "boom"

            def plan(self, finding):
                raise RuntimeError("boom")

        ctrl = TuneController(
            [Boom(), make_builtin_policy("stage-hot-files")],
            cooldown_s=0.0)
        assert len(ctrl.on_findings([finding(rank=0)])) == 1


# ----------------------------------------------------------------- applier
class TestApplier:
    def test_duplicate_delivery_skipped(self):
        app = TuneApplier(rank=0)
        a = TuneAction(action_id="a1", kind="resize-threads",
                       params={"threads": 4})
        first = app.apply(a)
        again = app.apply(a)
        assert first.status == "rejected"      # no control bound
        assert again.status == "skipped"
        assert again.detail == "duplicate delivery"

    def test_dry_run_snapshots_and_changes_nothing(self):
        from repro.data.pipeline import PipelineControl
        control = PipelineControl(threads=4)
        app = TuneApplier(rank=0, pipeline_control=control)
        ack = app.apply(TuneAction(action_id="a1", kind="resize-threads",
                                   params={"threads": 8}), dry_run=True)
        assert ack.status == "dry-run"
        assert ack.before == {"threads": 4}
        assert control.take_request() is None

    def test_resize_directive_scales_current(self):
        from repro.data.pipeline import PipelineControl
        control = PipelineControl(threads=4)
        app = TuneApplier(rank=0, pipeline_control=control)
        ack = app.apply(TuneAction(
            action_id="a1", kind="resize-threads",
            params={"direction": "up", "factor": 2}))
        assert ack.status == "applied" and ack.after["threads"] == 8
        assert control.take_request() == 8
        ack = app.apply(TuneAction(
            action_id="a2", kind="resize-threads",
            params={"direction": "down", "factor": 16}))
        assert ack.after["threads"] == 1       # clamped at >= 1

    def test_migrate_stages_small_files(self, tmp_path):
        from repro.data.synthetic import make_imagenet_like
        from repro.data.tiers import default_tiers
        tm = default_tiers(str(tmp_path))
        paths = make_imagenet_like(str(tmp_path / "hdd" / "d"),
                                   n_files=6, seed=1)
        app = TuneApplier(rank=0, tier_manager=tm, dataset=paths)
        ack = app.apply(TuneAction(
            action_id="a1", kind="migrate-file",
            params={"tier": "optane", "size_threshold": 2 << 20}))
        assert ack.status == "applied"
        assert ack.after["migrated_files"] == 6
        for p in paths:
            dst = app.resolve(p)
            assert dst != p and tm.tier_of(dst).name == "optane"
            with open(p, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read()
        # re-issue: already-migrated files are not copied again
        ack2 = app.apply(TuneAction(
            action_id="a2", kind="migrate-file",
            params={"tier": "optane", "size_threshold": 2 << 20}))
        assert ack2.after["migrated_files"] == 0

    def test_migrate_without_bindings_rejected(self):
        ack = TuneApplier(rank=0).apply(TuneAction(
            action_id="a1", kind="migrate-file", params={}))
        assert ack.status == "rejected"

    def test_throttle_checkpoint(self, tmp_path):
        from repro.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager(str(tmp_path / "ck"))
        app = TuneApplier(rank=0, checkpoint_manager=ckpt)
        ack = app.apply(TuneAction(
            action_id="a1", kind="throttle-checkpoint",
            params={"min_interval_s": 3.5}))
        assert ack.status == "applied"
        assert ckpt.min_interval_s == 3.5

    def test_failure_becomes_failed_ack(self):
        class BadControl:
            @property
            def current_threads(self):
                raise RuntimeError("boom")

        app = TuneApplier(rank=0, pipeline_control=BadControl())
        ack = app.apply(TuneAction(action_id="a1", kind="resize-threads",
                                   params={"direction": "up"}))
        assert ack.status == "failed" and "boom" in ack.detail

    def test_bind_rejects_unknown_knob(self):
        with pytest.raises(ValueError):
            TuneApplier(rank=0).bind(gpu_clock=3.0)

    def test_current_applier_publication(self):
        app = TuneApplier(rank=0)
        set_current_applier(app)
        try:
            assert current_applier() is app
        finally:
            set_current_applier(None)
        assert current_applier() is None


# --------------------------------------------------- checkpoint throttling
class TestCheckpointThrottle:
    def test_async_saves_spaced(self, tmp_path):
        from repro.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager(str(tmp_path / "ck"), keep=10)
        tree = {"w": __import__("numpy").zeros((4,))}
        assert ckpt.save_async(1, tree)
        ckpt.wait()
        prev = ckpt.set_throttle(60.0)
        assert prev == 0.0
        assert not ckpt.save_async(2, tree)    # inside the interval
        assert ckpt.throttle_skipped == 1
        ckpt.set_throttle(0.0)
        assert ckpt.save_async(3, tree)        # throttle off again
        ckpt.wait()
        assert ckpt.latest_step() == 3

    def test_skipped_save_does_not_wait_for_the_writer(self, tmp_path,
                                                       monkeypatch):
        import threading
        from repro.train import checkpoint as ckpt_mod
        release = threading.Event()
        write = ckpt_mod._write_atomic

        def held_write(path, data):
            release.wait(30)
            write(path, data)

        monkeypatch.setattr(ckpt_mod, "_write_atomic", held_write)
        ckpt = ckpt_mod.CheckpointManager(str(tmp_path / "ck"), keep=10)
        tree = {"w": __import__("numpy").zeros((4,))}
        ckpt.set_throttle(60.0)
        try:
            assert ckpt.save_async(1, tree)
            t = time.monotonic()
            assert not ckpt.save_async(2, tree)    # in flight: recent
            assert time.monotonic() - t < 1.0
            assert ckpt._thread.is_alive()         # it did not join
            assert ckpt.throttle_skipped == 1
        finally:
            release.set()
        ckpt.wait()
        assert ckpt.latest_step() == 1

    def test_throttle_counts_from_the_last_call(self, tmp_path, monkeypatch):
        from repro.train import checkpoint as ckpt_mod
        from types import SimpleNamespace
        clock = [100.0]
        monkeypatch.setattr(ckpt_mod, "time",
                            SimpleNamespace(monotonic=lambda: clock[0]))
        ckpt = ckpt_mod.CheckpointManager(str(tmp_path / "ck"), keep=10)
        tree = {"w": __import__("numpy").zeros((4,))}
        ckpt.set_throttle(10.0)
        assert ckpt.save_async(1, tree)
        ckpt.wait()
        clock[0] = 109.0                           # 9 s after the call
        assert not ckpt.save_async(2, tree)
        clock[0] = 110.0
        assert ckpt.save_async(3, tree)
        ckpt.wait()
        assert ckpt.latest_step() == 3

    def test_sync_save_never_throttled(self, tmp_path):
        from repro.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager(str(tmp_path / "ck"), keep=10)
        tree = {"w": __import__("numpy").zeros((2,))}
        ckpt.set_throttle(60.0)
        ckpt.save(1, tree)
        ckpt.save(2, tree)                     # the final save must land
        assert ckpt.latest_step() == 2


# ------------------------------------------------------- pipeline control
class TestPipelineControl:
    def test_autotune_honors_external_request(self):
        from repro.data.pipeline import AUTOTUNE, Pipeline, PipelineControl
        control = PipelineControl()
        seen = []

        def fn(i):
            seen.append(control.current_threads)
            return b"x" * 64

        control.request_threads(7)
        pipe = (Pipeline(list(range(160)))
                .map(fn, AUTOTUNE)
                .with_control(control))
        list(pipe)
        # the request lands at a window boundary: the second window
        # runs with exactly the requested count (the climb continues
        # from there afterwards)
        assert 7 in seen

    def test_take_request_is_once(self):
        from repro.data.pipeline import PipelineControl
        c = PipelineControl()
        c.request_threads(3)
        assert c.take_request() == 3
        assert c.take_request() is None


# ------------------------------------------------------------- local loop
class TestLocalLoop:
    def test_facade_closed_loop_migrates(self, tmp_path):
        from repro.core import reset_runtime
        from repro.data.synthetic import make_imagenet_like
        from repro.data.tiers import default_tiers, make_tiered_reader
        from repro.profiler import Profiler, ProfilerOptions
        tm = default_tiers(str(tmp_path))
        paths = make_imagenet_like(str(tmp_path / "hdd" / "d"),
                                   n_files=24, seed=2)
        prof = Profiler(ProfilerOptions(insight=True, tune=True),
                        runtime=reset_runtime())
        with prof:
            assert prof.bind_tune(dataset=paths, tier_manager=tm)
            reader = make_tiered_reader(
                tm, resolver=prof.tune_applier.resolve)
            for p in paths:
                reader(p)
            applied = prof.tune_tick()
        assert applied >= 1
        assert prof.tune_applier.stats["migrated_files"] == 24
        audit = prof.report.tune_audit
        assert any(e["status"] == "acked"
                   and e["action"]["kind"] == "migrate-file"
                   for e in audit)
        assert "tune_audit" in prof.report.to_dict()

    def test_bind_tune_noop_when_off(self):
        from repro.profiler import Profiler
        prof = Profiler()
        assert prof.bind_tune(dataset=[]) is False
        assert prof.tune_tick() == 0

    def test_loop_tick_applies_and_acks(self):
        class FakeEngine:
            def __init__(self):
                self.findings = []

            def poll(self):
                return []

        engine = FakeEngine()
        ctrl = make_controller()
        app = TuneApplier(rank=0)
        loop = LocalTuneLoop(engine, ctrl, app, rank=0)
        assert loop.tick() == 0
        engine.findings.append(finding(rank=0))
        assert loop.tick() == 1
        assert ctrl.entries[0].status == "acked"
        assert loop.tick() == 0                # acked: nothing pending


# ----------------------------------------------------------------- options
class TestOptions:
    def test_tune_requires_insight(self):
        from repro.profiler import ProfilerOptions
        from repro.profiler.options import ProfilerOptionsError
        with pytest.raises(ProfilerOptionsError):
            ProfilerOptions(tune=True).validate()

    def test_tune_knobs_require_tune(self):
        from repro.profiler import ProfilerOptions
        from repro.profiler.options import ProfilerOptionsError
        with pytest.raises(ProfilerOptionsError):
            ProfilerOptions(tune_policies=("stage-hot-files",)).validate()
        with pytest.raises(ProfilerOptionsError):
            ProfilerOptions(tune_dry_run=True).validate()

    def test_unknown_policy_fails_fast(self):
        from repro.profiler import Profiler, ProfilerOptions, registry
        with pytest.raises(registry.RegistryError):
            Profiler(ProfilerOptions(insight=True, tune=True,
                                     tune_policies=("nope",)))

    def test_intervals_validated(self):
        from repro.profiler import ProfilerOptions
        from repro.profiler.options import ProfilerOptionsError
        with pytest.raises(ProfilerOptionsError):
            ProfilerOptions(insight=True, tune=True,
                            tune_cooldown_s=-1.0).validate()
        with pytest.raises(ProfilerOptionsError):
            ProfilerOptions(insight=True, tune=True,
                            tune_interval_s=0.0).validate()

    def test_register_policy_decorator(self):
        from repro.profiler import register_policy, registry

        @register_policy("test-noop-policy", override=True)
        def make(opts):
            class Noop:
                name = "test-noop-policy"

                def plan(self, finding):
                    return []
            return Noop()

        assert "test-noop-policy" in registry.get_registry("policy")
        assert registry.create(
            "policy", "test-noop-policy", None).plan(finding()) == []

    def test_action_kinds_stable(self):
        assert ACTION_KINDS == ("migrate-file", "resize-threads",
                                "throttle-checkpoint", "io-chunk")
