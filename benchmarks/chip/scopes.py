"""Device time of the train step by the layer names the program gives it.

The program names each layer of its train step with ``jax.named_scope``
(``SCOPES``) and each Pallas kernel with its ``name=``.  A TPU profiler
trace keeps, for every device operation, the JAX name stack it came
from: the ``tf_op`` stat of the operation's event metadata in the
``.xplane.pb`` file.  ``jax.profiler.ProfileData`` gives events but not
the stats of their metadata, so this module reads the metadata itself
with a small reader of the protobuf wire format, and keys it by the
metadata's name, which is the event name ``ProfileData`` gives.

A fusion carries one ``tf_op``, its root operation's, so all of a
fusion's time goes to the scope of its root.  An operation belongs to
the innermost scope of ``SCOPES`` on its name stack; one under none of
them is ``unscoped``.  The events are those ``trace_reduce.load_events``
keeps, clipped to ``bench.window`` as ``trace_reduce.reduce_events``
clips them, so that:

* ``digital`` (seconds per scope of every operation that is not a
  kernel, ``trace_reduce.kernel_of``) adds up to ``other_s``;
* ``kernels`` (seconds per scope of each kernel's operations) adds up to
  each kernel's time;
* ``reads`` splits the read kernel's time into ``forward``,
  ``backward`` (under JAX's ``transpose`` of the loss) and ``recompute``
  (a forward read replayed by the rematerialised backward of the layer
  scan, under JAX's ``rematted_computation`` label).

The reads need no program scope and are found in any trace; the scopes
are ``named`` only where some operation carries one.
"""
from __future__ import annotations

import glob
import re
import sys
from pathlib import Path

import bench
import trace_reduce

SCOPES = ("xbar.read", "xbar.write", "xbar.carry", "attention",
          "head_loss", "layer_scan", "layer")
UNSCOPED = "unscoped"
REMAT = "rematted_computation"
TRANSPOSE = "transpose"
TF_OP = "tf_op"
DEVICE_PLANE = "/device:TPU:"

# XSpace.planes; XPlane.name, .event_metadata, .stat_metadata;
# XEventMetadata.name, .stats; XStat.metadata_id, .str_value, .ref_value;
# XStatMetadata.name; a map entry's key and value.
_PLANES, _NAME, _EVENT_MD, _STAT_MD = 1, 2, 4, 5
_MD_STATS = 5
_STAT_ID, _STR_VALUE, _REF_VALUE = 1, 5, 7
_KEY, _VALUE = 1, 2


def _varint(buf: bytes, i: int) -> tuple:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of one message in ``buf[i:end]``; a
    length-delimited value is its (start, end), left unread."""
    while i < end:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield tag >> 3, value


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, span: tuple) -> tuple:
    key = value = None
    for f, v in _fields(buf, *span):
        if f == _KEY:
            key = v
        elif f == _VALUE:
            value = v
    return key, value


def op_scopes(path: str) -> dict:
    """{device operation's event name: its ``tf_op``} over the TPU
    planes of one ``.xplane.pb`` file."""
    buf = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _PLANES:
            continue
        name, events, stat_md = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == _NAME:
                name = _text(buf, v)
            elif pf == _EVENT_MD:
                events.append(_map(buf, v)[1])
            elif pf == _STAT_MD:
                key, md = _map(buf, v)
                stat_md[key] = md
        if not name.startswith(DEVICE_PLANE):
            continue
        names = {}
        for key, md in stat_md.items():
            for sf, v in _fields(buf, *md):
                if sf == _NAME:
                    names[key] = _text(buf, v)
        tf_op = {k for k, n in names.items() if n == TF_OP}
        for ev in events:
            ev_name = op = None
            for mf, v in _fields(buf, *ev):
                if mf == _NAME:
                    ev_name = _text(buf, v)
                elif mf == _MD_STATS:
                    stat = dict(_fields(buf, *v))
                    if stat.get(_STAT_ID) not in tf_op:
                        continue
                    if _STR_VALUE in stat:
                        op = _text(buf, stat[_STR_VALUE])
                    elif _REF_VALUE in stat:
                        op = names.get(stat[_REF_VALUE])
            if ev_name is not None and op:
                out[ev_name] = op
    return out


def _stack(tf_op: str) -> list:
    """The names on a ``tf_op``'s stack, transforms unwrapped:
    ``a/transpose(jvp(b))/c:`` gives a, transpose, jvp, b, c."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    return [t for t in re.split(r"[/()]", path) if t]


def scope_of(tf_op: str) -> str:
    for name in reversed(_stack(tf_op)):
        if name in SCOPES:
            return name
    return UNSCOPED


def read_kind(tf_op: str) -> str:
    stack = _stack(tf_op)
    if REMAT in stack:
        return "recompute"
    return "backward" if TRANSPOSE in stack else "forward"


def reduce_events(chips: list, spans: list, tf_ops: dict) -> dict:
    """Seconds per scope and per kind of read inside the window (see the
    module docstring), averaged over the chips traced."""
    windows = [(s, e) for s, e, n in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows or not chips:
        raise ValueError("trace holds no bench.window span or no device "
                         "operation")
    w0, w1 = windows[0]
    digital: dict = {}
    kernels = {k: {} for k in trace_reduce.KERNELS}
    reads = {"forward": 0.0, "backward": 0.0, "recompute": 0.0}
    for ops in chips:
        for s, e, n in ops:
            if e <= w0 or s >= w1:
                continue
            d = (min(e, w1) - max(s, w0)) * 1e-9
            op = tf_ops.get(n, "")
            scope = scope_of(op)
            k = trace_reduce.kernel_of(n)
            into = digital if k is None else kernels[k]
            into[scope] = into.get(scope, 0.0) + d
            if k == "xbar_vmm":
                reads[read_kind(op)] += d
    n = len(chips)

    def per_chip(d: dict) -> dict:
        return {k: v / n for k, v in d.items()}

    named = any(s != UNSCOPED for d in (digital, *kernels.values())
                for s in d)
    return {"window_s": (w1 - w0) * 1e-9, "named": named,
            "digital": per_chip(digital),
            "kernels": {k: per_chip(v) for k, v in kernels.items()},
            "reads": per_chip(reads)}


def reduce_file(path: str) -> dict:
    return reduce_events(*trace_reduce.load_events(path), op_scopes(path))


def table(red: dict, steps: int) -> str:
    """The split in ms a step, one line per scope, for stderr."""
    ms = lambda s: f"{1e3 * s / steps:.3f}"
    lines = [f"device ms a step by scope ({steps} steps; "
             f"named: {red['named']})"]
    for scope in SCOPES + (UNSCOPED,):
        parts = [f"{k} {ms(red['kernels'][k][scope])}"
                 for k in trace_reduce.KERNELS if scope in red["kernels"][k]]
        if scope in red["digital"] or parts:
            lines.append(f"  {scope}: digital "
                         f"{ms(red['digital'].get(scope, 0.0))}"
                         + "".join(f", {p}" for p in parts))
    lines.append("  reads: " + ", ".join(f"{k} {ms(v)}"
                                         for k, v in red["reads"].items()))
    return "\n".join(lines)


def _run_trace(window_s: float):
    """The profiler trace a benchmark run just wrote: the newest file
    under the checkout's trace directory whose ``bench.window`` is the
    run's (to the nanosecond), or None."""
    files = glob.glob(str(bench.ROOT / ".bench_trace" / "**"
                          / "*.xplane.pb"), recursive=True)
    for path in sorted(files, key=lambda p: Path(p).stat().st_mtime,
                       reverse=True):
        red = reduce_file(path)
        if red["window_s"] == window_s:
            return red
    return None


def for_run(run: dict):
    """The scope split of a traced run, kept under ``run["trace"]
    ["scopes"]``: reduced from the run's own trace the first time a
    metric asks (the table goes to stderr), None without a trace."""
    t = run.get("trace")
    if not t or not run.get("steps"):
        return None
    if "scopes" not in t:
        t["scopes"] = _run_trace(t["window_s"])
        if t["scopes"] is not None:
            print(table(t["scopes"], run["steps"]), file=sys.stderr,
                  flush=True)
    return t["scopes"]


def digital_ms_per_step(run: dict, *scopes: str):
    """Device ms a step of the non-kernel operations in ``scopes``; None
    where the program names no scope or none of these."""
    red = for_run(run)
    if red is None or not red["named"] \
            or not any(s in red["digital"] for s in scopes):
        return None
    return 1e3 * sum(red["digital"].get(s, 0.0) for s in scopes) \
        / run["steps"]
