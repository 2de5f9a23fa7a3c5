"""The harness measures the chip only: a CPU, too few chips or a chip
missing from the peak table end the run with no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402


def test_refuses_a_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        bench.require_devices(1)


def test_refuses_an_unknown_device_kind():
    with pytest.raises(SystemExit, match="not in peaks.json"):
        bench.peaks_for("TPU v99 imaginary")
    assert bench.peaks_for("TPU v5 lite")["flops_per_s"] == 1.97e14


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-135m.train", "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_run_without_a_tpu_exits_with_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "no TPU" in r.stderr


def test_run_from_the_benchmark_files_alone_exits_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)
