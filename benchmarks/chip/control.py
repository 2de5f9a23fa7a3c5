"""The correctness check's control: the reference itself, put in the
program's place and computed in a lower precision, driven through a whole
run of the cell.  Its readings have to come out as not correct; the
lowest of them is the upper reading each limit is set below.

    python3 benchmarks/chip/control.py --workload smollm-135m.train \
        --precision high --seeds 11 12 13 [--seconds 1]

The reference's step takes the program's step's place, and its crossbar
read the place of the program's read.  Prints one JSON line per seed
with the compared numbers.
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


class ReferenceStep:
    """``step(state, batch, key)`` of the reference at ``precision``, with
    the program's calling convention (a train state with ``params`` and
    ``step``; metrics with ``loss``)."""

    def __init__(self, ref_mod, model: dict, dev: dict, lr: float,
                 precision: str):
        import jax

        def body(state, batch, key):
            params, loss = ref_mod.sgd_step(
                state["params"], batch["tokens"], batch["labels"], key,
                state["step"], model=model, dev=dev, lr=lr,
                precision=precision)
            return {"params": params, "step": state["step"] + 1}, \
                {"loss": loss}

        self._step = jax.jit(body, donate_argnums=(0,))

    def __call__(self, state, batch, key):
        return self._step(state, batch, key)

    @property
    def compiles(self) -> int:
        return self._step._cache_size()


def _reference(cell: dict):
    conf = cell["config"]
    return bench.load_module(
        HERE / "reference" / f"{conf['reference']}.py", "reference_ctl")


def control_factory(cell: dict, precision: str):
    conf = cell["config"]
    model = {**conf["model"], **cell["traffic"]["model"]}
    return lambda cfg, lr: ReferenceStep(_reference(cell), model,
                                         conf["device"], lr, precision)


def control_reader(cell: dict, precision: str):
    """The reference's crossbar read at ``precision``, in the place of
    the program's read."""
    import jax
    ref_mod, dev = _reference(cell), cell["config"]["device"]
    return jax.jit(lambda x, g, r, w, t: ref_mod.analog_read(
        x, g, r, w, dev, t, precision), static_argnums=4)


def main(argv=None) -> None:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", default="high")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    driver = bench.load_module(
        HERE / "drivers" / f"{cell['traffic']['kind']}.py", "driver")
    for seed in args.seeds:
        run_args = types.SimpleNamespace(seed=seed, seconds=args.seconds,
                                         trace=0)
        result, checks = driver.run(
            cell, run_args, t_start,
            factory=control_factory(cell, args.precision),
            reader=control_reader(cell, args.precision))
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "correct": result["correct"], "checks": checks}),
              flush=True)
        t_start = time.perf_counter()


if __name__ == "__main__":
    main()
