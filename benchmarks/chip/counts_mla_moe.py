"""Operations and bytes of the DeepSeek-V2 train step (MLA, leading dense
layers, expert layers with held experts), from a configuration's
unpadded widths, as ``counts.py`` does for a dense decoder.  Padding to
whole tiles, extra precision passes, recomputation and the rows of the
dropless buffers past each expert's routed ones are not counted.

* analog matrices: MLA's ``wq``, ``wkv_a``, ``wkv_b``, ``wo`` in every
  layer; the dense layers' ``w_upgate`` and ``w_down``; the expert
  layers' shared ``w_upgate`` and ``w_down``, and each held expert's
  ``w_upgate`` ([up | gate]) and ``w_down``, which see ``T * top_k /
  n_routed_experts`` of a step's T tokens on average: all held experts
  of a matrix together ``n_experts * top_k / n_routed_experts`` of the
  tokens (0.75 for 8 of 64 at top-6);
* model operations per token (for ``mfu``): 6*K*N for each analog matrix
  weighted by the share of tokens it sees, 6*d*vocab for the head, and
  6*S*H*(qk + v) per layer for the attention products (QK^T at the
  192-wide query-key heads, PV at the 128-wide values, forward and
  backward, full S);
* the held experts' reads per step: one forward and one backward read of
  each expert matrix over its expected rows, 2*rows*K*N operations each,
  moving g and ref (float32) and the rows' drive and output (float32);
* their writes per step: 2*rows*K*N operations, reading and writing g
  and reading the rows of both tapes.
"""
from __future__ import annotations

F32 = 4


def _share(model: dict) -> float:
    """Tokens each held expert sees, as a share of the step's."""
    width = model.get("n_routed_experts") or model["n_experts"]
    return model["top_k"] / width


def analog_matrices(model: dict) -> list:
    """[(name, matrices, K, N, share of the tokens each sees)]."""
    d, h = model["d_model"], model["n_heads"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], \
        model["v_head_dim"]
    r = model["kv_lora_rank"]
    dense = model.get("n_dense_layers", 0)
    lyr = model["n_layers"]
    moe = lyr - dense
    ffe = model["d_ff_expert"]
    sh = model["n_shared_experts"] * ffe
    e = model["n_experts"]
    return [
        ("wq", lyr, d, h * (dn + dr), 1.0),
        ("wkv_a", lyr, d, r + dr, 1.0),
        ("wkv_b", lyr, r, h * (dn + dv), 1.0),
        ("wo", lyr, h * dv, d, 1.0),
        ("w_upgate", dense, d, 2 * model["d_ff"], 1.0),
        ("w_down", dense, model["d_ff"], d, 1.0),
        ("shared/w_upgate", moe, d, 2 * sh, 1.0),
        ("shared/w_down", moe, sh, d, 1.0),
        ("experts/w_upgate", moe * e, d, 2 * ffe, _share(model)),
        ("experts/w_down", moe * e, ffe, d, _share(model)),
    ]


def ops_per_token(model: dict, seq: int) -> float:
    d = model["d_model"]
    qk = model["qk_nope_dim"] + model["qk_rope_dim"]
    attn = 6 * seq * model["n_heads"] * (qk + model["v_head_dim"]) \
        * model["n_layers"]
    mats = sum(6 * n * k * m * share
               for _, n, k, m, share in analog_matrices(model))
    return mats + 6 * d * model["vocab"] + attn


def _experts(model: dict) -> list:
    return [m for m in analog_matrices(model)
            if m[0].startswith("experts/")]


def expert_read_cost(model: dict, tokens: int) -> tuple:
    """(operations, bytes) of one step's forward and backward reads of
    the held experts, each over its expected routed rows."""
    ops = byts = 0.0
    for _, n, k, m, share in _experts(model):
        rows = tokens * share
        ops += n * 2 * (2 * rows * k * m)
        byts += n * 2 * F32 * (2 * k * m + rows * (k + m))
    return ops, byts


def expert_write_cost(model: dict, tokens: int) -> tuple:
    """(operations, bytes) of one step's writes of the held experts."""
    ops = byts = 0.0
    for _, n, k, m, share in _experts(model):
        rows = tokens * share
        ops += n * 2 * rows * k * m
        byts += n * F32 * (2 * k * m + rows * (k + m))
    return ops, byts
