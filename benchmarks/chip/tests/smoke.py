"""A cell at test size, run on the CPU without the harness's look for a
chip: smollm-135m's layout at d_model 64 on 32x32 tiles."""
from __future__ import annotations

import json
import sys
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402

SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab": 256,
         "analog_rows": 32, "analog_cols": 32}
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
# At this size on the CPU the program's jnp twins and the reference
# associate alike, so a sound run agrees to about 1e-6 (bit for bit in
# most steps) and a rounding anywhere shows; the chip's limits
# (limits/<cell>.json) sit far higher because there the kernels'
# rounding differences are amplified by the 8-bit periphery (PERF.md).
SMALL_LIMITS = {"loss_gap": 1e-5, "first_change_gap": 1e-5,
                "change3_gap": 1e-5, "read_code_share": 0.0}


def small_cell(config: str = "smollm-135m", traffic: str = "train",
               batch=(4, 32)) -> dict:
    conf = bench.load_json(HERE / "configs" / f"{config}.json")
    conf["model"].update(SMALL)
    conf["device"].update(rows=32, cols=32)
    conf["train_batch"] = list(batch)
    tr = bench.load_json(HERE / "traffic" / f"{traffic}.json")
    tr["pool"] = 4
    return {"name": "small", "chips": 1, "config": conf, "traffic": tr,
            "limits": SMALL_LIMITS,
            "end_to_end": [{"name": "setup_s", "unit": "s"},
                           {"name": "train_tokens_per_s", "unit": "-"}],
            "per_layer": []}


def run_small(cell: dict, seed: int = 3, seconds: float = 0.5,
              monkeypatch=None, **kw) -> tuple:
    """Drive the whole run on the CPU; returns (result, checks).  ``kw``
    goes to ``drivers/<kind>.run`` (a ``factory`` or a ``reader``)."""
    import jax
    devs = jax.devices()
    fake = lambda n: (devs[:n], PEAKS)
    if monkeypatch is not None:
        monkeypatch.setattr(bench, "require_devices", fake)
    else:
        bench.require_devices = fake
    driver = bench.load_module(
        HERE / "drivers" / f"{cell['traffic']['kind']}.py", "driver_small")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return driver.run(cell, args, time.perf_counter(), **kw)


if __name__ == "__main__":
    res, checks = run_small(small_cell())
    print(json.dumps({**res, "checks": checks}))
