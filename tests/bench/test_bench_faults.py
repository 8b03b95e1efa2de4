"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program (``_fault_main.py``) and the rest of
the run, the check included, goes as on the chip, at the smoke sizes on
the CPU.  No cell spans chips, so the fault of a left-out exchange between
chips has no cell to break."""

import os

import pytest

import benchproc

MAIN = os.path.join("tests", "bench", "_fault_main.py")


@pytest.mark.parametrize(
    "fault,cell",
    [
        ("frozen_step", "qwen2-1.5b.offline"),
        ("half_batch", "qwen2-1.5b.offline"),
        ("half_batch_tail", "qwen2-1.5b.offline"),
        ("altered_answer", "qwen2-1.5b.offline"),
        ("frozen_step", "hymba-1.5b.offline"),
        ("half_batch", "hymba-1.5b.offline"),
        ("half_batch_tail", "hymba-1.5b.offline"),
        ("altered_answer", "hymba-1.5b.offline"),
    ],
)
def test_fault_is_not_correct(fault, cell, tmp_path):
    line = benchproc.last_line(benchproc.run([MAIN, fault, cell, "7"], tmp_path))
    assert line["correct"] is False, line["compared"]
    c = line["compared"]["x0_relerr_max"]
    assert c["value"] > c["limit"]


def test_unbroken_path_is_correct(tmp_path):
    line = benchproc.last_line(
        benchproc.run([MAIN, "none", "qwen2-1.5b.offline", "7"], tmp_path))
    assert line["correct"] is True, line["compared"]
