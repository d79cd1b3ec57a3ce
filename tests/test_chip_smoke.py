"""chip_smoke.py off the chip: it must fail fast and say why.

What the script proves on a TPU (training, serving, numerics at full
width) cannot run here; what can be pinned on the CPU is the other half
of its contract — no accelerator, no result line, non-zero exit.  The
suite's process is pinned to the CPU (conftest), which is all
``JAX_PLATFORMS=cpu python chip_smoke.py`` would change, so the script is
run here as ``__main__`` without paying for another interpreter.
"""

import os
import runpy
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu_and_names_the_platform(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exit_info:
        runpy.run_path(os.path.join(REPO, "chip_smoke.py"),
                       run_name="__main__")
    assert exit_info.value.code not in (0, None)
    out, err = capsys.readouterr()
    assert "platform is 'cpu'" in err
    assert out == ""
