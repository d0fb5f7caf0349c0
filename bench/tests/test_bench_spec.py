"""BENCHMARK.json resolves by name, keeps to its contract, and grows by
new files and entries alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec

BENCH = spec.BENCH_DIR
ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
PER_LAYER = [m["name"] for m in BENCHMARK["per_layer"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_workload_resolves(workload):
    cell = spec.resolve(workload)
    assert cell.config["name"] == cell.config_name
    assert spec.reference(cell.config).flops_per_step
    for key in ("batch", "seq", "warmup_steps", "steps", "profile_every"):
        assert key in cell.traffic, key
    names = {m.name for m in cell.metrics}
    assert "setup_s" in names
    assert any(m.kind == "end_to_end" and m.name != "setup_s"
               for m in cell.metrics)
    assert any(m.kind == "per_layer" for m in cell.metrics)
    for m in cell.metrics:
        assert callable(spec.reader(m.name)), m.name


@pytest.mark.parametrize("metric", PER_LAYER)
def test_moves_is_reported_in_each_of_its_cells(metric):
    m = next(x for x in BENCHMARK["per_layer"] if x["name"] == metric)
    for workload in m.get("workloads", CELLS):
        reported = {x.name for x in spec.resolve(workload).metrics
                    if x.kind == "end_to_end"}
        assert m["moves"] in reported, (metric, workload)


def test_benchmark_keeps_to_its_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank")) and NAME.match(k)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["name"] not in names
            names.add(m["name"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for n in [w["name"] for w in b["workloads"]] + [c["name"]
                                                    for c in b["configs"]]:
        assert NAME.match(n), n


def test_a_new_cell_resolves_from_new_files_alone(tmp_path):
    """A later change adds a configuration, a traffic mix, a metric and a
    cell as new files and new entries; nothing that exists is edited."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    b = json.loads(json.dumps(BENCHMARK))
    cfg = json.loads((bench_dir / "configs" / "mamba2-370m.json").read_text())
    cfg.update(name="mamba2-370m-wide")
    (bench_dir / "configs" / "mamba2-370m-wide.json").write_text(
        json.dumps(cfg))
    tr = json.loads((bench_dir / "traffic" / "steady.json").read_text())
    tr.update(seq=4096, batch=2)
    (bench_dir / "traffic" / "long.json").write_text(json.dumps(tr))
    (bench_dir / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return len(run.window_steps)\n")
    b["configs"].append({"name": "mamba2-370m-wide", "source": "x",
                         "file": "bench/configs/mamba2-370m-wide.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "mamba2-370m-wide.long",
                           "config": "mamba2-370m-wide", "traffic": "long",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "device step", "moves": "tokens_per_s",
                           "workloads": ["mamba2-370m-wide.long"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve("mamba2-370m-wide.long", root=str(tmp_path),
                        bench_dir=str(bench_dir))
    assert cell.traffic["seq"] == 4096
    assert "steps_in_window" in {m.name for m in cell.metrics}

    class FakeRun:
        window_steps = range(5, 9)

    read = spec.reader("steps_in_window", bench_dir=str(bench_dir))
    assert read(FakeRun()) == 4
    for name in os.listdir(BENCH):
        if name in (".work", "__pycache__"):
            continue
        assert (bench_dir / name).exists()


def test_without_a_tpu_the_harness_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs 1 TPU" in proc.stderr


def test_a_split_quantity_is_read_by_its_base_reader(tmp_path):
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "steps.py").write_text("def read(run):\n    return 1\n")
    assert spec.reader("steps.ckpt", bench_dir=str(tmp_path))(None) == 1
    (metrics / "steps.ckpt.py").write_text("def read(run):\n    return 2\n")
    assert spec.reader("steps.ckpt", bench_dir=str(tmp_path))(None) == 2


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("cpu")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
