"""chip_smoke.py's checks at toy widths on the CPU backend, and its
refusal to report success without a TPU."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_checks_pass_at_reduced_size(chip_smoke, tmp_path, capsys,
                                     monkeypatch):
    # keep this worker's compiles out of the persistent cache
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "off")
    chip_smoke.smoke(tmp_path / "run", reduced=True)
    out = capsys.readouterr().out
    assert f"step {chip_smoke.STEPS} loss" in out
    assert "restored bit-equal" in out
    assert "profile shard reads 0" not in out


def test_check_raises_on_a_miss(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")
