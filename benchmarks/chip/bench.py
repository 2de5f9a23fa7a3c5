"""Shared pieces of the chip benchmark: a cell and its files, the device
rule, the compile cache, the per-layer metric readers and the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
at the checkout root, its configuration at the ``file`` that entry
names, its traffic mix at ``traffic/<traffic>.json``, its correctness
limits at ``limits/<cell>.json``, the run of the mix's kind at
``drivers/<kind>.py``, the reference at ``reference/<reference>.py`` and
each per-layer metric's reader at ``metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports."""
    bench = load_json(root / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    wl = found[0]
    entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {
        "name": name,
        "chips": wl["chips"],
        "config": load_json(root / entry["file"]),
        "traffic": load_json(HERE / "traffic" / f"{wl['traffic']}.json"),
        "limits": load_json(HERE / "limits" / f"{name}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json; add "
                         f"its published peaks before measuring on it")
    return table[kind]


def require_devices(n: int):
    """The chips this cell runs on, and their peaks.  Anything but a TPU,
    or fewer chips than the cell asks for, ends the run with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} devices; "
                         f"this benchmark measures the chip only")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n], peaks_for(devs[0].device_kind)


def set_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key from a seed of up to 63 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_per_layer(cell: dict, run: dict) -> dict:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          "metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check(checks: dict, name: str, value: float, limit: float) -> bool:
    """Record one compared number beside its limit; NaN fails."""
    checks[name] = {"value": value, "limit": limit}
    return math.isfinite(value) and value <= limit


def emit(result: dict, checks: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    line (with the same numbers under ``checks``, last) on stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)
