"""Mixture-of-Experts FFN with sort-based token dispatch.

The layer holds ``n_experts`` experts, global indices ``first_expert ..
first_expert + n_experts - 1`` of the ``router_width`` the router scores:
one chip's share of an expert-parallel layer, or every expert.  It
routes each token over all of them (softmax, greedy top-k, the gates
renormalised only with ``norm_topk_prob``), dispatches the assignments
to its own experts, and returns their part of the output plus the
shared (always-on) experts (DeepSeek-V2).  What absent experts would add
is left to their holders.

Dispatch sorts the assignments by expert and gathers each expert's rows
into a (E, C, d) buffer (static shapes, no (T, E, C) one-hot tensor);
the combine gathers each token's k outputs back.  In analog device mode
the layer is dropless: C is the token count, which no routing overflows
since a token picks an expert at most once, and each expert's routed
row count rides to the crossbar kernels so they read and write only
those rows.  The digital and fakequant paths keep a capacity of
``capacity_factor`` times the mean load (the sharded dry runs at the
assigned shapes, whose dropless buffers would not fit) and drop the
assignments past it.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.tiled_analog import (crossbar_from_model,
                                     is_analog_container, readout)

from .layers import (dense_init, expert_project, ffn, ffn_init,
                     proj_from_weights, project)

Array = jax.Array


def moe_init(key: Array, cfg: ModelConfig) -> dict:
    """Router (digital — it gates, it never carries a stationary matmul
    worth a tile grid) over ``router_width`` experts + the FFN matrices
    of the ``n_experts`` held.  In analog device mode the expert stacks
    are programmed onto *expert-batched* tiled-crossbar containers — one
    tile grid and one calibration per expert, the expert dim riding the
    layer-batched update kernel grid (PANTHER-style: every stationary
    weight matrix lives in-array, not just attention/FFN).  Each expert's
    up and gate matrices lie side by side on one grid, ``w_upgate`` =
    [up | gate], as a dense gated FFN's do (``ffn_init``): both halves
    share the row drives."""
    ffe = cfg.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 4)
    e_keys = jax.random.split(ks[0], 3)

    def stack(k, d_in, d_out):
        return jax.vmap(lambda kk: dense_init(kk, d_in, d_out))(
            jax.random.split(k, cfg.n_experts))

    def program(w):
        return proj_from_weights(w, cfg) if cfg.analog_training else w

    p = {
        "router": {"w": dense_init(ks[1], cfg.d_model, cfg.router_width)},
        "experts": {
            "w_upgate": program(jnp.concatenate(
                [stack(e_keys[0], cfg.d_model, ffe),
                 stack(e_keys[1], cfg.d_model, ffe)], axis=-1)),
            "w_down": program(stack(e_keys[2], ffe, cfg.d_model)),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(ks[2], cfg,
                               d_ff=cfg.n_shared_experts * ffe)
    return p


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Rows per expert of the digital and fakequant dispatch buffers:
    ``capacity_factor`` times the mean load, padded to a multiple of 8."""
    c = int(np.ceil(cfg.capacity_factor * n_tokens * cfg.top_k
                    / cfg.router_width))
    return max(8, -(-c // 8) * 8)


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Dispatch rows per expert: every token in device mode (dropless),
    else :func:`expert_capacity`."""
    if cfg.analog_training:
        return n_tokens
    return expert_capacity(n_tokens, cfg)


def stats_zero() -> dict:
    """Routing stats of no layer: the balance loss, the assignments
    routed to held experts, the most rows one held expert received
    (``max_*`` entries combine over layers by maximum) and the held
    assignments dropped past the capacity."""
    i = jnp.zeros((), jnp.int32)
    return {"aux": jnp.zeros((), jnp.float32), "moe_routed_rows": i,
            "max_expert_rows": i, "moe_dropped": i}


def moe_apply(p: dict, x: Array, cfg: ModelConfig
              ) -> Tuple[Array, Array]:
    """Returns (output, aux_loss) — aux is the switch load-balancing loss.
    :func:`moe_layer` also returns the dispatch counters."""
    y, stats = moe_layer(p, x, cfg)
    return y, stats["aux"]


def moe_layer(p: dict, x: Array, cfg: ModelConfig) -> Tuple[Array, dict]:
    """Returns (output, routing stats of :func:`stats_zero`).

    K4 (perf): REPRO_MOE_GROUPS=G dispatches within G independent batch
    groups (vmap) instead of one global sort.  With G = the data-parallel
    degree, routing/sort/scatter stay shard-local and the only cross-device
    movement is the expert-dim resharding (the true EP all-to-all), instead
    of global gathers of the (T·k, d) dispatch tensors."""
    groups = int(os.environ.get("REPRO_MOE_GROUPS", "1"))
    if cfg.analog_training:
        # Device mode always dispatches globally: the grouped/vmapped
        # formulations would apply (or batch-trace) each expert container
        # more than once per step, breaking the one-application tape
        # contract of core/tiled_analog.
        return _moe_apply_flat(p, x, cfg)
    if groups > 1 and x.shape[0] % groups == 0:
        if os.environ.get("REPRO_MOE_EXPLICIT"):
            return _moe_apply_grouped(p, x, cfg, groups)
        bg = x.shape[0] // groups
        xg = x.reshape(groups, bg, *x.shape[1:])
        yg, sg = jax.vmap(lambda xx: _moe_apply_flat(p, xx, cfg))(xg)
        return yg.reshape(x.shape), {
            "aux": jnp.mean(sg["aux"]),
            "moe_routed_rows": jnp.sum(sg["moe_routed_rows"]),
            "max_expert_rows": jnp.max(sg["max_expert_rows"]),
            "moe_dropped": jnp.sum(sg["moe_dropped"])}
    return _moe_apply_flat(p, x, cfg)


def _route(p: dict, xt: Array, cfg: ModelConfig):
    """(gates (T, k), global expert ids (T, k), balance loss): softmax
    over the router's width, greedy top-k; the Switch loss is
    ``width * sum_i f_i p_i`` over the top-1 choices."""
    with jax.named_scope("moe.route"):
        logits = project(p["router"], xt.astype(jnp.float32),
                         cfg.digital())
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk_prob:
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        width = probs.shape[-1]
        me = jnp.mean(probs, axis=-2)
        ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], width,
                                     dtype=jnp.float32), axis=-2)
        aux = width * jnp.sum(me * ce, axis=-1)
    return top_p, top_i, aux


def _shard_ge(buf: Array) -> Array:
    """Constrain a (G, E, ...) dispatch buffer to (dp, model, ...) so the
    expert einsum and its backward stay shard-local (K4-explicit)."""
    from repro.core.shardctx import get_shard_context
    mesh, dp, tp = get_shard_context()
    if mesh is None or dp is None:
        return buf
    import numpy as _np
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp_t = dp if isinstance(dp, tuple) else (dp,)
    dp_size = int(_np.prod([mesh.shape[a] for a in dp_t]))
    spec = [None] * buf.ndim
    if buf.shape[0] % dp_size == 0:
        spec[0] = dp
    if buf.shape[1] % mesh.shape[tp] == 0:
        spec[1] = tp
    return jax.lax.with_sharding_constraint(
        buf, NamedSharding(mesh, P(*spec)))


def _moe_apply_grouped(p: dict, x: Array, cfg: ModelConfig, groups: int
                       ) -> Tuple[Array, dict]:
    """K4-explicit: grouped dispatch with a first-class group axis so the
    (G, E, C, d) buffers can carry (data, model) sharding constraints —
    the vmap formulation cannot express them, and XLA otherwise gathers
    the buffers across the mesh in the expert-einsum backward."""
    b, s, d = x.shape
    t_all = b * s
    tg = t_all // groups
    k, e = cfg.top_k, cfg.n_experts
    xt = x.reshape(groups, tg, d)

    top_p, top_i, aux = _route(p, xt, cfg)
    aux = jnp.mean(aux)

    local = top_i.reshape(groups, tg * k) - cfg.first_expert
    held = (local >= 0) & (local < e)
    flat_e = jnp.where(held, local, e)
    flat_w = top_p.reshape(groups, tg * k).astype(x.dtype)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tg), k)[None], (groups, tg * k))
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    st = jnp.take_along_axis(flat_t, order, axis=-1)
    sw = jnp.take_along_axis(flat_w, order, axis=-1)
    g_idx = jnp.broadcast_to(jnp.arange(groups)[:, None],
                             (groups, tg * k))
    counts = jnp.zeros((groups, e + 1), jnp.int32).at[g_idx, flat_e].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((groups, 1), jnp.int32),
         jnp.cumsum(counts, axis=-1)[:, :-1]], axis=-1)
    pos = jnp.arange(tg * k)[None] - jnp.take_along_axis(offsets, se,
                                                         axis=-1)
    cap = _capacity(tg, cfg)
    keep = (pos < cap) & (se < e)
    pos_w = jnp.where(keep, pos, cap)

    buf = jnp.zeros((groups, e, cap, d), dtype=x.dtype)
    xt_rows = jnp.take_along_axis(xt, st[..., None], axis=1)
    buf = buf.at[g_idx, se, pos_w].set(xt_rows, mode="drop")
    buf = _shard_ge(buf)

    ew = p["experts"]
    act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
    # expert_project vmapped over the group axis: the digital path lowers
    # to the same gecd,edf->gecf einsums as before, and fakequant mode
    # now threads the crossbar I/O quantisation through the grouped
    # dispatch too (the grouped path never runs in device mode).
    up, gate = jnp.split(jax.vmap(
        lambda bg: expert_project(ew["w_upgate"], bg, cfg))(buf), 2, axis=-1)
    out_buf = jax.vmap(
        lambda hg: expert_project(ew["w_down"], hg, cfg))(act(gate) * up)
    out_buf = _shard_ge(out_buf)

    gathered = out_buf[g_idx, jnp.minimum(se, e - 1), pos_w] \
        * (sw * keep.astype(x.dtype))[..., None]
    y = jnp.zeros((groups, tg, d), dtype=x.dtype).at[g_idx, st].add(
        gathered)
    if "shared" in p:
        from .layers import ffn as _ffn
        y = y + _ffn(p["shared"], xt, cfg)
    routed = jnp.sum(keep).astype(jnp.int32)
    return y.reshape(b, s, d), {
        "aux": aux, "moe_routed_rows": routed,
        "max_expert_rows": jnp.max(jnp.minimum(counts[:, :e], cap)),
        "moe_dropped": jnp.sum(held).astype(jnp.int32) - routed}


def _moe_apply_flat(p: dict, x: Array, cfg: ModelConfig
                    ) -> Tuple[Array, dict]:
    b, s, d = x.shape
    t = b * s
    k, e = cfg.top_k, cfg.n_experts
    xt = x.reshape(t, d)
    top_p, top_i, aux = _route(p, xt, cfg)

    # --- dispatch: sort the assignments by held expert (the others last),
    # then gather each expert's rows into its slots, token order kept ---
    with jax.named_scope("moe.dispatch"):
        local = top_i.reshape(-1) - cfg.first_expert        # (t*k,)
        held = (local >= 0) & (local < e)
        key = jnp.where(held, local, e)
        order = jnp.argsort(key, stable=True)
        counts = jnp.bincount(key, length=e + 1)
        offsets = jnp.cumsum(counts) - counts
        rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        pos = rank - offsets[key]
        cap = _capacity(t, cfg)
        keep = held & (pos < cap)
        rows = jnp.minimum(counts[:e], cap)
        slot = jnp.arange(cap)
        filled = slot[None, :] < rows[:, None]                 # (e, cap)
        src = order[jnp.minimum(offsets[:e, None] + slot[None, :],
                                t * k - 1)] // k
        buf = jnp.where(filled[..., None], xt[src], 0).astype(x.dtype)

    # --- expert FFN, batched over the (shardable) expert dim -----------------
    # expert_project dispatches: raw (E, d, f) einsum stacks (digital /
    # fakequant) or expert-batched crossbar containers (device mode —
    # forward VMM / backward MVM per expert array over its routed rows).
    with jax.named_scope("moe.experts"):
        ew = p["experts"]
        up, gate = jnp.split(expert_project(ew["w_upgate"], buf, cfg, rows),
                             2, axis=-1)
        act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
        hidden = act(gate) * up
        out_buf = expert_project(ew["w_down"], hidden, cfg, rows)

    # --- combine: each token gathers its k outputs, weighted by its gates
    with jax.named_scope("moe.dispatch"):
        gates = jnp.where(keep, top_p.reshape(-1), 0).astype(x.dtype)
        got = out_buf[jnp.clip(local, 0, e - 1), jnp.minimum(pos, cap - 1)]
        y = jnp.sum((gates[:, None] * got).reshape(t, k, d), axis=1)

    if "shared" in p:
        y = y + ffn(p["shared"], xt, cfg)
    routed = jnp.sum(keep).astype(jnp.int32)
    return y.reshape(b, s, d), {
        "aux": aux, "moe_routed_rows": routed,
        "max_expert_rows": jnp.max(rows).astype(jnp.int32),
        "moe_dropped": jnp.sum(held).astype(jnp.int32) - routed}


def moe_dense_reference(p: dict, x: Array, cfg: ModelConfig) -> Array:
    """Oracle: compute every held expert densely and mask by top-k (tests)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = xt.astype(jnp.float32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs)
    gates = jax.vmap(lambda g, i, v: g.at[i].set(v))(gates, top_i, top_p)
    gates = gates[:, cfg.first_expert:cfg.first_expert + cfg.n_experts]
    ew = p["experts"]
    if is_analog_container(ew["w_down"]):  # serial-read containers (tests)
        xc = crossbar_from_model(cfg)
        ew = {k: readout(ew[k], xc) for k in ("w_upgate", "w_down")}
    act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
    up, gate = jnp.split(jnp.einsum("td,edf->etf", xt,
                                    ew["w_upgate"].astype(xt.dtype)),
                         2, axis=-1)
    out = jnp.einsum("etf,efd->etd", act(gate) * up,
                     ew["w_down"].astype(xt.dtype))
    y = jnp.einsum("etd,te->td", out, gates.astype(xt.dtype))
    if "shared" in p:
        y = y + ffn(p["shared"], xt, cfg)
    return y.reshape(b, s, d)
