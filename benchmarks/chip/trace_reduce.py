"""Reduce a JAX profiler trace of a measured window to the numbers the
per-layer metrics read.

From the ``.xplane.pb`` file the profiler writes:

* device operations: the events of each TPU plane's ``XLA Ops`` line
  (start, duration, name).  Busy time is the union of their intervals
  inside the window, averaged over the chips traced;
* the window: the benchmark's own host span ``bench.window``;
* kernel time: the summed durations of the kernels' events, per kernel
  (``kernel_of``); the rest is ``other_s``.  Control-flow operations
  (``while``, ``conditional``, ``call``) span the operations of their
  bodies and are left out of every sum and of the busy union;
* ``breakdown``: the ten device operations that took most time, and the
  ten longest idle gaps inside the window, each named by the benchmark
  host span (``bench.*``) that covers most of it.
"""
from __future__ import annotations

import glob
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
KERNELS = ("xbar_vmm", "xbar_update")
# A TPU trace names each device operation by its HLO instruction text.
# The Pallas kernels carry no name of their own there (today's program
# gives neither pallas_call a ``name=``): both are
# ``custom_call_target="tpu_custom_call"``.  They are told apart by
# their operands: the rank-k write takes the uint32 PRNG seed and tile
# offsets (``u32[4]``), the fused read does not.
TPU_KERNEL = 'custom_call_target="tpu_custom_call"'
WRITE_OPERAND = "u32[4]"
CONTROL_FLOW = (" while(", " conditional(", " call(")


def kernel_of(name: str):
    if TPU_KERNEL not in name:
        return None
    operands = name.split(" custom-call(", 1)[-1].split(TPU_KERNEL, 1)[0]
    return "xbar_update" if WRITE_OPERAND in operands else "xbar_vmm"


def short_name(name: str) -> str:
    """A device operation's instruction name without its number (or the
    kernel's name), so that a breakdown adds up like operations."""
    k = kernel_of(name)
    if k is not None:
        return k
    head = name.split(" = ", 1)[0].lstrip("%")
    return head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() \
        else head


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load_events(path: str) -> tuple:
    """(device ops per chip [[(start_ns, end_ns, name)]], host spans
    [(start_ns, end_ns, name)]) from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events
                            if not any(c in ev.name for c in CONTROL_FLOW)]
            if ops:
                chips.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    return chips, spans


def reduce_events(chips: list, spans: list) -> dict:
    """The trace's numbers (seconds), see the module docstring."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows or not chips:
        raise ValueError("trace holds no bench.window span or no device "
                         "operation")
    w0, w1 = windows[0]
    kernels = {k: 0.0 for k in KERNELS}
    other = busy = 0.0
    totals: dict = {}
    gaps = []
    for ops in chips:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        for s, e, n in inside:
            d = (e - s) * 1e-9
            k = kernel_of(n)
            if k is None:
                other += d
            else:
                kernels[k] += d
            short = short_name(n)
            totals[short] = totals.get(short, 0.0) + d
        merged = _union([[s, e] for s, e, _ in inside])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(chips)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy / n,
        "window_s": (w1 - w0) * 1e-9,
        "kernels": {k: v / n for k, v in kernels.items()},
        "other_s": other / n,
        "breakdown": {
            "device_ops": [[name, t / n] for name, t in top_ops],
            "idle_gaps": [[_host_doing(s, e, spans), (e - s) * 1e-9]
                          for s, e in top_gaps],
        },
    }


def _host_doing(s: int, e: int, spans: list) -> str:
    """The innermost benchmark span covering most of [s, e)."""
    best, cover, best_len = "host outside bench spans", 0, float("inf")
    for a, b, name in spans:
        if name == WINDOW_SPAN:
            continue
        c = min(b, e) - max(a, s)
        if c > cover or (c == cover and c > 0 and b - a < best_len):
            best, cover, best_len = name, c, b - a
    return best


def reduce_dir(trace_dir, steps: int) -> dict:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise ValueError(f"no profiler trace under {trace_dir}")
    out = reduce_events(*load_events(files[-1]))
    out["steps"] = steps
    return out
