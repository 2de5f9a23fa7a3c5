"""The operation and byte counts against hand-worked values."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import counts  # noqa: E402


def _model(name):
    return bench.load_json(HERE / "configs" / f"{name}.json")["model"]


def test_smollm_135m_cells_and_ops_per_token():
    m = _model("smollm-135m")
    # per layer 576x960 (GQA: 9+3+3 heads of 64) + 576x576
    # + 576x3072 (gate and up) + 1536x576, 30 layers
    assert counts.analog_cells(m) == 30 * 3_538_944 == 106_168_320
    # 6*cells + 6*576*49152 head + 30 layers * 12*256*576 attention
    assert counts.ops_per_token(m, 256) == \
        6 * 106_168_320 + 6 * 576 * 49152 + 30 * 12 * 256 * 576
    assert abs(counts.ops_per_token(m, 256) / 1e9 - 0.860) < 1e-3


def test_starcoder2_3b_cells_and_ops_per_token():
    m = _model("starcoder2-3b")
    # per layer 3072x3584 (GQA: 24+2+2 heads of 128) + 3072x3072
    # + 3072x12288 + 12288x3072, 6 layers
    assert counts.analog_cells(m) == 6 * 95_944_704 == 575_668_224
    assert abs(counts.ops_per_token(m, 2048) / 1e9 - 4.81) < 5e-3


def test_kernel_costs_by_hand():
    m = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
         "d_ff": 8, "vocab": 16, "n_layers": 3, "gated": False}
    mats = counts.analog_matrices(m)
    assert [(k, n) for _, _, k, n in mats] == [(4, 8), (4, 4), (4, 8), (8, 4)]
    cells = 3 * (32 + 16 + 32 + 32)
    t = 10
    ops, byts = counts.read_cost(m, t)
    assert ops == 2 * 2 * t * cells
    assert byts == 3 * 2 * 4 * (2 * (32 + 16 + 32 + 32)
                                + t * (12 + 8 + 12 + 12))
    ops_w, byts_w = counts.write_cost(m, t, pulse_train=False)
    assert ops_w == 2 * t * cells and byts_w == byts // 2
    assert counts.write_cost(m, t, pulse_train=True)[0] == 4 * t * cells
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000.0, 50.0, peaks) == (10.0, "compute")
    assert counts.least_time(100.0, 50.0, peaks) == (5.0, "memory")
