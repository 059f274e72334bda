"""``chip_smoke.py`` refuses to run, and prints no result, without a TPU.

The script is the quickest proof that the training round still runs on
the chip; a run on any other platform must fail instead of passing on the
CPU.  Both checks run in this process: they stop at the device check,
before any model is built.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("count", [1, 4])
def test_device_check_refuses_cpu(chip_smoke, count):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.require_tpu(count)


def test_main_fails_without_ok_line(chip_smoke, capsys, tmp_path):
    assert chip_smoke.main(["--out", str(tmp_path)]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err
