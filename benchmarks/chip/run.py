"""Chip benchmark of the analog training simulator.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints one JSON object as the last line of stdout: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number the correctness check compared, with its limit.
Refuses (non-zero exit, no result) without a TPU, with fewer chips than
the cell asks for, or on a chip missing from ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# The TPU runtime's own logs stay inside the checkout too.
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(HERE.parents[1] / ".bench_trace"
                                    / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

import bench  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    driver = bench.load_module(
        HERE / "drivers" / f"{cell['traffic']['kind']}.py", "driver")
    result, checks = driver.run(cell, args, T_START)
    bench.emit(result, checks)


if __name__ == "__main__":
    main()
