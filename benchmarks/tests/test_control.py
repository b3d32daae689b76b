"""The control, at a size a test run can hold: the reference at fp8 in the
program's place has to come out over a limit, the program itself under all."""

import pytest

from benchmarks import study
from benchmarks.harness.loader import read_benchmark

CELLS = [w["name"] for w in read_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, capsys):
    lines = study.main(
        ["--workload", cell, "--seeds", "5,2147483700,3000000019", "--seconds", "1", "--control", "--rehearse"]
    )
    capsys.readouterr()
    for line in lines:
        assert line["control_over"], f"the control passed every limit on seed {line['seed']}: {line['control']}"
        assert not line["program_over"], line["program"]
        assert set(line["control"]) >= {"vec_err", "topk_gap", "score_err", "e2e_gap"}
    assert study.verdict(lines) == 0
    passed = [dict(line, control_over=[]) for line in lines]
    assert study.verdict(passed) == 1  # a control that passes is what study.py exits on
    capsys.readouterr()
