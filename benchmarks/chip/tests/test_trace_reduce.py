"""The trace reduction and every per-layer metric, on a small profiler
trace recorded on a TPU v5e (smollm-135m.train, a window of a few
steps), committed gzipped beside this file."""
import gzip
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "smollm-135m.train"
DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / f"{CELL}.xplane.pb.gz"
META = bench.load_json(DATA / f"{CELL}.trace.json")
STEPS = META["steps"]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(RECORDED) as src, open(d / "chip.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.reduce_dir(d.parents[2], STEPS)


def test_reduction_finds_both_kernels_and_the_window(reduced):
    assert reduced["window_s"] > 0.0
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["kernels"]["xbar_vmm"] > 0.0
    assert reduced["kernels"]["xbar_update"] > 0.0
    assert reduced["other_s"] > 0.0
    # kernels and the rest add up to no more than the busy time
    total = sum(reduced["kernels"].values()) + reduced["other_s"]
    assert total <= reduced["busy_s"] * 1.0001
    bd = reduced["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "xbar_vmm"


def test_every_per_layer_metric_reads(reduced):
    cell = bench.load_cell(CELL)
    conf = cell["config"]
    b, s = conf["train_batch"]
    run = {"steps": STEPS,
           "tokens_per_s": STEPS * b * s / META["window_s"],
           "peaks": bench.peaks_for("TPU v5 lite"),
           "model": conf["model"], "seq": s, "batch": b,
           "pulse_train": False, "trace": reduced}
    got = bench.read_per_layer(cell, run)
    assert set(got) == {m["name"] for m in cell["per_layer"]}
    for name, m in got.items():
        assert m["value"] > 0.0, name
        if m["unit"] == "%":
            assert m["value"] <= 100.0, name


def test_a_reader_with_nothing_to_read_returns_none(reduced):
    empty = {**reduced, "kernels": {"xbar_vmm": 0.0, "xbar_update": 0.0}}
    cell = bench.load_cell(CELL)
    conf = cell["config"]
    b, s = conf["train_batch"]
    run = {"steps": STEPS, "peaks": bench.peaks_for("TPU v5 lite"),
           "model": conf["model"], "seq": s, "batch": b,
           "pulse_train": False, "trace": empty, "tokens_per_s": 1.0}
    got = bench.read_per_layer(cell, run)
    assert "xbar_vmm_roofline.train" not in got
    assert "xbar_update_roofline" not in got


def test_kernel_names_as_the_trace_shows_them():
    read = ('%closed_call.73 = f32[1,2048,3072]{2,1,0:T(8,128)} custom-call('
            'f32[1,2048,1024]{2,1,0:T(8,128)} %pad.575, f32[1,2]{1,0:T(1,128)'
            'S(1)} %pad_maximum_fusion.46), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={}')
    write = ('%_step_impl.5 = f32[12,1024,3072]{2,1,0:T(8,128)} custom-call('
             'f32[12,2048,1024]{2,1,0:T(8,128)} %pad.92, u32[4]{0:T(128)S(1)}'
             ' %get-tuple-element.1311), custom_call_target="tpu_custom_call"')
    assert trace_reduce.kernel_of(read) == "xbar_vmm"
    assert trace_reduce.kernel_of(write) == "xbar_update"
    assert trace_reduce.kernel_of("%fusion.3 = f32[8] fusion(f32[8] %a)") \
        is None
    assert trace_reduce.short_name("%fusion.130 = s32[2] fusion()") == \
        "fusion"
