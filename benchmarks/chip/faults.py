"""Faults a training cell can have, planted under a whole run of the cell
so that the correctness check can be seen to fail on each.

    python3 benchmarks/chip/faults.py --workload smollm-135m.train \
        --fault half_batch --seeds 21 22 23

* ``state_unchanged``: the step returns the state it was given;
* ``half_batch``: the step sees the first half of the batch only (of its
  rows, or of its positions when it has one row), its mean taken over
  those tokens.

(One chip has no exchange between chips to leave out.)  Prints one JSON
line per seed with the compared numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402


def _program(cfg, lr):
    from repro.train.analog_lm import make_analog_sgd_step
    return make_analog_sgd_step(cfg, lr=lr)


class StateUnchanged:
    """Runs the program's step on a copy and hands back the old state."""

    def __init__(self, cfg, lr):
        self.inner = _program(cfg, lr)
        self.compiles = 1

    def __call__(self, state, batch, key):
        import jax
        import jax.numpy as jnp
        _, mets = self.inner(jax.tree.map(jnp.copy, state), batch, key)
        return state, mets


class HalfBatch:
    """Runs the program's step on the first half of the batch's rows (of
    its positions, for a batch of one row)."""

    def __init__(self, cfg, lr):
        self.inner = _program(cfg, lr)

    @property
    def compiles(self):
        return self.inner.compiles

    def __call__(self, state, batch, key):
        b, s = batch["tokens"].shape
        cut = (lambda v: v[:b // 2]) if b > 1 else (lambda v: v[:, :s // 2])
        return self.inner(state, {k: cut(v) for k, v in batch.items()}, key)


FAULTS = {"state_unchanged": StateUnchanged, "half_batch": HalfBatch}


def main(argv=None) -> None:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    driver = bench.load_module(
        HERE / "drivers" / f"{cell['traffic']['kind']}.py", "driver")
    for seed in args.seeds:
        run_args = types.SimpleNamespace(seed=seed, seconds=args.seconds,
                                         trace=0)
        result, checks = driver.run(cell, run_args, t_start,
                                    factory=FAULTS[args.fault])
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": result["correct"], "checks": checks}),
              flush=True)
        t_start = time.perf_counter()


if __name__ == "__main__":
    main()
