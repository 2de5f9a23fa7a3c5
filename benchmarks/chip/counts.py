"""Operations and bytes the algorithm needs, from a configuration's
unpadded widths.  Padding to whole tiles, extra precision passes and
recomputation are not counted, so a change that removes them moves the
measured time and not these counts.

* model operations per token (for ``mfu``): 6*K*N for each analog matrix
  (the forward read, the backward read and the rank-k write), 6*d*vocab
  for the head, and 12*S*H*D per layer for the attention products at
  sequence length S (QK^T and PV, forward and backward, full S);
* fused read kernel, per training step: one forward (VMM) and one
  backward (MVM) read of every matrix, 2*T*K*N operations each, moving
  g and ref (float32) and the drive and the output (float32);
* rank-k write kernel, per step: 2*T*K*N operations (4*T*K*N for
  pulse trains, which also accumulate |x||d|), reading and writing g and
  reading both tapes.
"""
from __future__ import annotations

F32 = 4


def analog_matrices(model: dict) -> list:
    """[(name, layers, K, N)] of the crossbar matrices of a dense decoder."""
    d, ff, lyr = model["d_model"], model["d_ff"], model["n_layers"]
    hd = model.get("head_dim") or d // model["n_heads"]
    nh, nkv = model["n_heads"], model["n_kv_heads"]
    mats = [("wqkv", lyr, d, (nh + 2 * nkv) * hd), ("wo", lyr, nh * hd, d)]
    if model["gated"]:
        mats.append(("w_upgate", lyr, d, 2 * ff))
    else:
        mats.append(("w_up", lyr, d, ff))
    mats.append(("w_down", lyr, ff, d))
    return mats


def analog_cells(model: dict) -> int:
    return sum(lyr * k * n for _, lyr, k, n in analog_matrices(model))


def ops_per_token(model: dict, seq: int) -> int:
    d = model["d_model"]
    hd = model.get("head_dim") or d // model["n_heads"]
    attn = 12 * seq * model["n_heads"] * hd * model["n_layers"]
    return 6 * analog_cells(model) + 6 * d * model["vocab"] + attn


def read_cost(model: dict, tokens: int) -> tuple:
    """(operations, bytes) of one training step's fused reads."""
    ops = byts = 0
    for _, lyr, k, n in analog_matrices(model):
        ops += lyr * 2 * (2 * tokens * k * n)
        byts += lyr * 2 * F32 * (2 * k * n + tokens * (k + n))
    return ops, byts


def write_cost(model: dict, tokens: int, pulse_train: bool) -> tuple:
    """(operations, bytes) of one step's rank-k writes."""
    ops = byts = 0
    for _, lyr, k, n in analog_matrices(model):
        ops += lyr * (4 if pulse_train else 2) * tokens * k * n
        byts += lyr * F32 * (2 * k * n + tokens * (k + n))
    return ops, byts


def least_time(ops: float, byts: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time."""
    t_c = ops / peaks["flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
