"""The split of a device trace by the program's layer names
(``scopes.py``) and the per-layer metrics that read it, on two profiler
traces recorded on a TPU v5e and committed gzipped beside this file:
``smollm-135m.train-carry-pulse`` (the program names its layers and
kernels; the window holds a carry sweep) and ``smollm-135m.train``
(recorded before the program named anything)."""
import gzip
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NAMED = "smollm-135m.train-carry-pulse"
UNNAMED = "smollm-135m.train"
NEW_METRICS = ("xbar_vmm.recompute_ms_per_step",
               "xbar_glue.device_ms_per_step",
               "attention.device_ms_per_step", "head_loss.device_ms_per_step",
               "layer_scan.device_ms_per_step", "carry.device_ms_per_step")
SCOPE_METRICS = NEW_METRICS[1:]


def _unpack(cell: str, root: Path) -> Path:
    """The recorded trace of ``cell`` where a run leaves its own:
    ``<root>/.bench_trace/<cell>/plugins/profile/<run>/``."""
    d = root / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = d / "chip.xplane.pb"
    with gzip.open(DATA / f"{cell}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


class Recorded:
    def __init__(self, cell: str, root: Path):
        self.cell = cell
        self.root = root
        self.path = _unpack(cell, root)
        self.meta = bench.load_json(DATA / f"{cell}.trace.json")
        self.steps = self.meta["steps"]
        self.events = trace_reduce.load_events(str(self.path))
        self.tf_ops = scopes.op_scopes(str(self.path))
        self.reduced = trace_reduce.reduce_events(*self.events)
        self.split = scopes.reduce_events(*self.events, self.tf_ops)

    def run(self, with_scopes: bool = True) -> dict:
        """A traced run's data as the train driver hands it to the
        metric readers."""
        conf = bench.load_cell(self.cell)["config"]
        b, s = conf["train_batch"]
        trace = {**self.reduced, "steps": self.steps}
        if with_scopes:
            trace["scopes"] = self.split
        return {"steps": self.steps,
                "tokens_per_s": self.steps * b * s / self.meta["window_s"],
                "peaks": bench.peaks_for("TPU v5 lite"),
                "model": conf["model"], "seq": s, "batch": b,
                "pulse_train": self.cell == NAMED, "trace": trace}


@pytest.fixture(scope="module")
def named(tmp_path_factory):
    return Recorded(NAMED, tmp_path_factory.mktemp("named"))


@pytest.fixture(scope="module")
def unnamed(tmp_path_factory):
    return Recorded(UNNAMED, tmp_path_factory.mktemp("unnamed"))


def test_stacks_as_the_trace_records_them():
    fwd = "jit(_step_impl)/jvp(layer_scan)/while/body/closed_call/layer/" \
          "attention/xbar.read/xbar_vmm/pallas_call:"
    bwd = "jit(_step_impl)/transpose(jvp(layer_scan))/while/body/" \
          "closed_call/checkpoint/layer/xbar.read/xbar_vmm/pallas_call:"
    remat = "jit(_step_impl)/transpose(jvp(layer_scan))/while/body/" \
            "closed_call/checkpoint/rematted_computation/layer/attention/" \
            "xbar.read/xbar_vmm/pallas_call:"
    assert [scopes.scope_of(t) for t in (fwd, bwd, remat)] == \
        ["xbar.read"] * 3
    assert [scopes.read_kind(t) for t in (fwd, bwd, remat)] == \
        ["forward", "backward", "recompute"]
    assert scopes.scope_of("jit(_step_impl)/transpose(jvp(layer_scan))/"
                           "while/body/dynamic_slice:") == "layer_scan"
    assert scopes.scope_of("jit(_step_impl)/transpose(jvp(head_loss))/"
                           "scatter-add:") == "head_loss"
    assert scopes.scope_of("jit(_step_impl)/jvp()/while/body/"
                           "closed_call/pallas_call:") == scopes.UNSCOPED
    # a scope's name must match whole: "layer" is not "layer_scan"
    assert scopes.scope_of("jit(f)/layer_scan/while/body/add:") \
        == "layer_scan"


def test_every_kernel_event_is_in_its_kernels_scope(named):
    want = {"xbar_vmm": "xbar.read", "xbar_update": "xbar.write"}
    seen = set()
    for ops in named.events[0]:
        for _, _, name in ops:
            k = trace_reduce.kernel_of(name)
            if k is not None:
                assert scopes.scope_of(named.tf_ops.get(name, "")) \
                    == want[k], name
                seen.add(k)
    assert seen == set(want)


@pytest.mark.parametrize("which", ["named", "unnamed"])
def test_both_partitions_add_up(which, request):
    rec = request.getfixturevalue(which)
    split, red = rec.split, rec.reduced
    assert split["window_s"] == red["window_s"]
    assert sum(split["digital"].values()) == \
        pytest.approx(red["other_s"], rel=1e-3)
    for k in trace_reduce.KERNELS:
        assert sum(split["kernels"][k].values()) == \
            pytest.approx(red["kernels"][k], rel=1e-3)
    assert sum(split["reads"].values()) == \
        pytest.approx(red["kernels"]["xbar_vmm"], rel=1e-3)
    assert min(split["reads"].values()) > 0.0


def test_named_trace_scopes_cover_the_digital_interior(named):
    digital = named.split["digital"]
    assert named.split["named"]
    assert set(digital) <= set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert digital.get(scopes.UNSCOPED, 0.0) \
        <= 0.1 * sum(digital.values())


def test_every_metric_reads_on_the_named_trace(named):
    cell = bench.load_cell(NAMED)
    got = bench.read_per_layer(cell, named.run())
    assert set(got) == {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= set(got)
    for name in NEW_METRICS:
        assert got[name]["value"] > 0.0, name
        assert got[name]["unit"] == "ms"
    digital = got["digital.device_ms_per_step"]["value"]
    parts = sum(got[m]["value"] for m in SCOPE_METRICS) \
        + 1e3 * sum(named.split["digital"].get(s, 0.0)
                    for s in ("layer", scopes.UNSCOPED)) / named.steps
    assert parts == pytest.approx(digital, rel=1e-3)


def test_only_the_recompute_split_reads_without_program_names(unnamed):
    run = unnamed.run()
    assert not run["trace"]["scopes"]["named"]
    for name in NEW_METRICS:
        mod = bench.load_module(HERE / "metrics" / f"{name}.py",
                                "metric_" + name.replace(".", "_"))
        value = mod.read(run)
        if name == "xbar_vmm.recompute_ms_per_step":
            # the layer scan's rematerialised forward reads
            assert 20.0 < value < 30.0
        else:
            assert value is None, name


def test_a_run_finds_its_own_trace(named, monkeypatch):
    """Without a split kept under the run's trace, the first reader
    reduces the trace of the run's own window from the checkout's trace
    directory and keeps it there; a trace of another window is not
    read."""
    monkeypatch.setattr(bench, "ROOT", named.root)
    run = named.run(with_scopes=False)
    assert scopes.for_run(run) == named.split
    assert run["trace"]["scopes"] == named.split
    other = named.run(with_scopes=False)
    other["trace"]["window_s"] += 1e-9
    assert scopes.for_run(other) is None
    monkeypatch.setattr(bench, "ROOT", named.root / "nothing")
    assert scopes.for_run(named.run(with_scopes=False)) is None
