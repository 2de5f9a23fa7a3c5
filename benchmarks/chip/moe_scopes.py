"""Device time of an expert layer's parts, by the names the program's MoE
layer gives them (``models/moe``, the expert writes in
``train/analog_lm``):

* ``moe.route``: the router's projection, softmax, top-k and the
  balance loss;
* ``moe.dispatch``: the sort of the assignments, the gather into the
  experts' buffers and the combine;
* ``moe.experts``: the held experts' reads and writes.

``scopes.SCOPES`` does not hold these names, so the metrics built on it
count these operations where they always did (``layer``, ``xbar.read``,
``xbar.write``).  Here an operation belongs to the innermost of the
names above on its stack (``scopes.op_scopes`` reads the stacks), and
the split is of the same events ``trace_reduce.load_events`` keeps,
clipped to ``bench.window``: ``digital`` (non-kernel seconds per name)
and ``kernels`` (each kernel's seconds per name).  A program that gives
none of these names reads as None.
"""
from __future__ import annotations

import glob
from pathlib import Path

import bench
import scopes
import trace_reduce

NAMES = ("moe.route", "moe.dispatch", "moe.experts")


def name_of(tf_op: str):
    for name in reversed(scopes._stack(tf_op)):
        if name in NAMES:
            return name
    return None


def reduce_events(chips: list, spans: list, tf_ops: dict) -> dict:
    windows = [(s, e) for s, e, n in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows or not chips:
        raise ValueError("trace holds no bench.window span or no device "
                         "operation")
    w0, w1 = windows[0]
    digital: dict = {}
    kernels = {k: {} for k in trace_reduce.KERNELS}
    for ops in chips:
        for s, e, n in ops:
            if e <= w0 or s >= w1:
                continue
            name = name_of(tf_ops.get(n, ""))
            if name is None:
                continue
            d = (min(e, w1) - max(s, w0)) * 1e-9
            k = trace_reduce.kernel_of(n)
            into = digital if k is None else kernels[k]
            into[name] = into.get(name, 0.0) + d
    n = len(chips)
    per_chip = lambda d: {k: v / n for k, v in d.items()}
    return {"window_s": (w1 - w0) * 1e-9, "digital": per_chip(digital),
            "kernels": {k: per_chip(v) for k, v in kernels.items()}}


def reduce_file(path: str) -> dict:
    return reduce_events(*trace_reduce.load_events(path),
                         scopes.op_scopes(path))


def for_run(run: dict):
    """The split of a traced run, kept under ``run["trace"]["moe"]``:
    reduced from the run's own trace (the newest whose ``bench.window``
    is the run's) the first time a metric asks; None without a trace or
    where the program names none of ``NAMES``."""
    t = run.get("trace")
    if not t or not run.get("steps"):
        return None
    if "moe" not in t:
        t["moe"] = None
        files = glob.glob(str(bench.ROOT / ".bench_trace" / "**"
                              / "*.xplane.pb"), recursive=True)
        for path in sorted(files, key=lambda p: Path(p).stat().st_mtime,
                           reverse=True):
            red = reduce_file(path)
            if red["window_s"] == t["window_s"]:
                named = red["digital"] or any(red["kernels"].values())
                t["moe"] = red if named else None
                break
    return t["moe"]


def kernel_s_per_step(run: dict, kernel: str):
    """Seconds a step of ``kernel`` under ``moe.experts``, or None."""
    red = for_run(run)
    if red is None:
        return None
    t = red["kernels"][kernel].get("moe.experts", 0.0)
    return t / run["steps"] if t > 0.0 else None
