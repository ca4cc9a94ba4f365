"""The controls come out not correct at a small size on the CPU: the plain
reference in bfloat16 in the program's place (`control.py`, which also
takes the readings on the chip at the cells' own sizes)."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import control  # noqa: E402
import cpu_run  # noqa: E402


@pytest.mark.parametrize("workload", ["engine.skew", "sim.table2"])
def test_control_is_not_correct(monkeypatch, workload):
    with control.reference_in_place():
        line = cpu_run.run(monkeypatch, workload, 3_000_000_029,
                           edit=control.edit_control)
    assert not line["correct"], line["checks"]
