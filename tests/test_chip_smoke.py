"""chip_smoke.py: it refuses to run without a TPU, and its phases pass on
the CPU at a small size (the same code the chip runs, minus the device
check)."""

import argparse
import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu_and_prints_no_result(chip_smoke, capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code not in (0, None)
    assert "needs a TPU" in str(ei.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_phases_pass_on_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "HIFI_READS", 4)
    chip_smoke.run(argparse.Namespace(seed=3, ref_len=100_000, chips=1))
    out = capsys.readouterr().out
    for phase in ("ranged reads rs1", "ranged reads hifi", "stream:", "token feed:",
                  "server:"):
        assert phase in out
    assert "second pass new traces=0" in out
