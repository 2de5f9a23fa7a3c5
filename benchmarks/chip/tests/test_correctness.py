"""The correctness check at a test size, on the CPU, with the harness's
look for a chip skipped: a sound run passes, and each planted fault a
cell can have, and the lower-precision control, come out as not
correct."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import smoke  # noqa: E402
import bench  # noqa: E402  (smoke puts the benchmark on sys.path)
import control  # noqa: E402
import faults  # noqa: E402

TRAFFIC = ["train", "train-carry-pulse"]
CELLS = {"train": "smollm-135m.train",
         "train-carry-pulse": "smollm-135m.train-carry-pulse"}
CASES = [(t, f) for t in TRAFFIC for f in faults.FAULTS]


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_sound_run_is_correct(monkeypatch, traffic):
    cell = smoke.small_cell(traffic=traffic)
    result, checks = smoke.run_small(cell, monkeypatch=monkeypatch,
                                     seconds=1.5)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["metrics"]) == 2


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_training_control_in_lower_precision_is_not_correct(monkeypatch,
                                                            traffic):
    """The reference at ``high`` (three bfloat16 passes) in the place of
    the program's step and read fails, and its reads fail the chip
    cell's own read limit, not only the test size's."""
    cell = smoke.small_cell(traffic=traffic)
    result, checks = smoke.run_small(
        cell, monkeypatch=monkeypatch,
        factory=control.control_factory(cell, "high"),
        reader=control.control_reader(cell, "high"))
    assert not result["correct"], checks
    chip = bench.load_json(bench.HERE / "limits"
                           / f"{CELLS[traffic]}.json")
    assert checks["read_code_share"]["value"] > chip["read_code_share"]


@pytest.mark.parametrize("traffic,fault", CASES)
def test_planted_fault_is_not_correct(monkeypatch, traffic, fault):
    cell = smoke.small_cell(traffic=traffic)
    result, checks = smoke.run_small(cell, monkeypatch=monkeypatch,
                                     seconds=1.5,
                                     factory=faults.FAULTS[fault])
    assert not result["correct"], checks
