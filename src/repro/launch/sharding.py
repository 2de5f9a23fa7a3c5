"""Sharding policy: parameters, optimizer state, batches, caches, and
tiled-crossbar analog containers.

The full policy narrative — TP/FSDP/EP/SP/DP rules, the divisibility
degradation, and the analog container tile-grid specs — lives in
``docs/sharding.md``.  In one line each:

  * TP over ``model``, FSDP over (pod, data), EP experts over ``model``,
    SP cache sequence over ``model``, DP batch over (pod, data);
  * analog containers shard at *whole-tile* granularity: row-tiles over
    the FSDP axes, column-tiles over ``model`` (mirroring the projection's
    TP split; flipped for row-parallel consumers), layer dim unsharded
    (it is the scan axis);
  * every rule degrades to replication when divisibility fails, so any
    (arch x mesh) pair lowers.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import os

from repro.configs.base import ModelConfig
from repro.core import analog_registry as registry
from repro.core.analog_registry import ANALOG_LEAVES  # noqa: F401  (re-export)

from .mesh import dp_axes


def _axis_size(mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def _fit(mesh, dim: int, names):
    """names if they divide dim, else None (replicate)."""
    if names is None:
        return None
    size = _axis_size(mesh, names)
    if size > 1 and dim % size == 0:
        return names if isinstance(names, str) or len(names) > 1 \
            else names[0]
    return None


def _tile_fit(mesh, dim: int, names, tile: int):
    """names if they divide ``dim`` at whole-*tile* granularity, else None.

    Analog containers may only split between physical crossbar tiles: a
    shard must own whole ``rows x cols`` arrays so the update kernel's
    per-(layer, tile) PRNG streams and the per-tile ADC stay local to one
    owner.  ``dim % (size * tile) == 0`` is therefore required — anything
    else degrades to replication, exactly like :func:`_fit`.
    """
    if names is None:
        return None
    size = _axis_size(mesh, names)
    if size > 1 and dim % (size * tile) == 0:
        return names if isinstance(names, str) or len(names) > 1 \
            else names[0]
    return None


#: Logical-axis names of the registry's container layouts -> mesh axes.
#: "ep" (the expert dim) consumes the model axis, mirroring the digital
#: EP rule; "fsdp" resolves to the (pod, data) axes of the mesh.
def _logical_axes(mesh, logical):
    if logical is None:
        return None
    if logical in ("tp", "ep"):
        return "model"
    if logical == "fsdp":
        return dp_axes(mesh)
    raise KeyError(logical)


def analog_container_pspec(sp, shape, cfg: ModelConfig, mesh,
                           leaf: str) -> P:
    """PartitionSpec for one leaf of a tiled-crossbar container.

    The *policy* lives in ``core.analog_registry.leaf_layout`` — per-dim
    (logical axis, tile granularity) derived from the container's path
    (consumer kind): column-tiles over ``model`` and row-tiles over the
    FSDP axes for column-parallel producers, flipped for row-parallel
    consumers (wo, w_down, out_proj), and for expert-batched containers
    the expert dim over ``model`` (EP) with row-tiles over FSDP and
    columns replicated.  This function only translates logical axes onto
    the concrete mesh, degrading any dim that does not divide at
    whole-tile granularity to replication (:func:`_tile_fit`).  The layer
    dim of a scan-stacked container is never sharded (it is the scan
    axis); ``w_scale`` follows its container's lead dims (per-expert
    scales live with their experts).  Tape slots follow their container:
    x_tape shards its K like g's rows, d_tape its N like g's columns, and
    the scale pairs ``s_tape`` its lead dims like ``w_scale``.
    """
    rows, cols = cfg.analog_rows, cfg.analog_cols
    kind = registry.classify(sp)
    layout = registry.leaf_layout(kind, len(shape), leaf, rows, cols)
    return P(*[_tile_fit(mesh, dim, _logical_axes(mesh, logical), tile)
               for dim, (logical, tile) in zip(shape, layout)])


def analog_update_specs(path: Tuple[str, ...], g_shape, cfg: ModelConfig,
                        mesh) -> Dict[str, P]:
    """PartitionSpecs for the shard_map'd rank-k write of one container.

    ``path`` is the container's key path in the parameter tree (used to
    pick the registry consumer kind); ``g_shape`` the (possibly
    scan-stacked / expert-batched) conductance shape.  Returns specs for g
    (also ref), the two code tapes and their scale pairs, the per-layer
    scale and the container's ``w_scale``, all tile-aligned so every shard
    owns whole tiles and the outer-product contraction (over tokens) stays
    local.
    """
    sp = list(path)
    lead = g_shape[:-2]
    k, n = g_shape[-2:]
    tapes_lead = (*lead, 1)  # (L, T, ...) / (T, ...): T never sharded
    w_scale_spec = analog_container_pspec(sp, lead, cfg, mesh, "w_scale")
    g_spec = analog_container_pspec(sp, g_shape, cfg, mesh, "g")
    return {
        "g": g_spec,
        # The optional carry (LSB) crossbar is sharded identically to its
        # primary: registry.leaf_layout maps both through the same rule.
        "g_carry": g_spec,
        "x_tape": analog_container_pspec(sp, (*tapes_lead, k), cfg, mesh,
                                         "x_tape"),
        "d_tape": analog_container_pspec(sp, (*tapes_lead, n), cfg, mesh,
                                         "d_tape"),
        "s_tape": analog_container_pspec(sp, (*lead, 2), cfg, mesh,
                                         "s_tape"),
        "scale": w_scale_spec,
        "w_scale": w_scale_spec,
    }


def param_pspec(path: Tuple, leaf, cfg: ModelConfig, mesh) -> P:
    """PartitionSpec for one parameter leaf (path from tree_map_with_path)."""
    keys = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
    sp = list(keys)
    shape = leaf.shape
    dp = dp_axes(mesh)

    def spec2d(d0_axes, d1_axes):
        """Spec for the trailing 2 dims; leading dims (layer/expert stacks)
        handled here."""
        lead = len(shape) - 2
        out = [None] * lead
        if "experts" in sp and lead >= 1:
            # EP: the expert dim takes the model axis; the inner matmul dims
            # only FSDP-shard (model is already consumed by the expert dim).
            out[lead - 1] = _fit(mesh, shape[lead - 1], "model")
            out.append(_fit(mesh, shape[-2], dp))
            out.append(None)
            return P(*out)
        out.append(_fit(mesh, shape[-2], d0_axes))
        out.append(_fit(mesh, shape[-1], d1_axes))
        return P(*out)

    # Tiled-crossbar containers (analog device mode): tile-granular split,
    # before every digital rule — including REPRO_FLAT_DP, whose arbitrary
    # largest-dim split would cut tiles in half.
    last_key = str(sp[-1]) if sp else ""
    if cfg.analog_training and last_key in ANALOG_LEAVES:
        return analog_container_pspec(sp, shape, cfg, mesh, last_key)

    # K6 (perf): pure ZeRO-3 — shard the largest divisible dim over the
    # flattened mesh; no tensor parallelism anywhere.
    if os.environ.get("REPRO_FLAT_DP"):
        out = [None] * len(shape)
        for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
            ax = _fit(mesh, shape[i], dp)
            if ax is not None:
                out[i] = ax
                break
        return P(*out)

    last = str(sp[-1])
    if last == "embed":
        return P(_fit(mesh, shape[0], "model"), _fit(mesh, shape[1], dp))
    if "lm_head" in sp:
        return spec2d(dp, "model")
    if last == "enc_pos":
        return P(None, None)
    if len(shape) < 2:
        return P(*([None] * len(shape)))
    # K2 (perf): the SSD in_proj output is split at segment boundaries that
    # do not align with a model-axis shard; TP forces a per-layer activation
    # all-gather.  REPRO_SSM_FSDP=1 switches SSM projections to ZeRO-3 style
    # sharding (per-layer *weight* gathers, ~100x smaller at batch 16x4096).
    if os.environ.get("REPRO_SSM_FSDP") and \
            any(k in sp for k in ("in_proj", "out_proj")):
        return spec2d(dp, None)
    # column-parallel producers (wqkv / w_upgate are the fused
    # self-attention and gated-FFN layouts: concat of column-parallel
    # pieces is itself column-parallel)
    if any(k in sp for k in ("wq", "wk", "wv", "wqkv", "w_up", "w_gate",
                             "w_upgate", "wkv_b", "in_proj", "xattn")):
        if "wo" in sp:  # xattn/wo handled below
            return spec2d("model", dp)
        return spec2d(dp, "model")
    # row-parallel consumers
    if any(k in sp for k in ("wo", "w_down", "out_proj")):
        return spec2d("model", dp)
    if "shared_in" in sp:
        return spec2d(dp, None)
    if "router" in sp or "wkv_a" in sp:
        return P(*([None] * len(shape)))
    if last in ("conv_w", "conv_b", "a_log", "d_skip", "dt_bias"):
        return P(*([None] * len(shape)))
    return P(*([None] * len(shape)))


def params_shardings(abstract_params, cfg: ModelConfig, mesh):
    def spec(path, leaf):
        # resolve nested attn dicts: path keys include the projection name
        return NamedSharding(mesh, param_pspec(path, leaf, cfg, mesh))
    return jax.tree_util.tree_map_with_path(spec, abstract_params)


def analog_params_shardings(abstract_params, cfg: ModelConfig, mesh):
    """Parameter shardings for the *sharded analog train step*.

    Tiled-crossbar containers split at tile granularity
    (:func:`analog_container_pspec`); every digital leaf — embeddings,
    norms, the logits head, exactly the parameters the paper keeps on the
    digital core — stays **replicated**.  The digital TP rules of
    :func:`param_pspec` would shard e.g. the tied embedding over
    (model, data) and turn the logits contraction into a partial-sum +
    all-reduce, whose association depends on the mesh; the analog step's
    bit-exact contract (same seed, any mesh, identical conductances)
    requires replicated digital compute instead.  The parallel axes of the
    analog step are the container tile grid, not the batch.
    """
    def spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
        last = keys[-1] if keys else ""
        if last in ANALOG_LEAVES:
            return NamedSharding(
                mesh, analog_container_pspec(keys, leaf.shape, cfg, mesh,
                                             last))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))
    return jax.tree_util.tree_map_with_path(spec, abstract_params)


def batch_shardings(abstract_batch, mesh):
    dp = dp_axes(mesh)

    def spec(path, leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        parts = [_fit(mesh, leaf.shape[0], dp)] + \
            [None] * (leaf.ndim - 1)
        return NamedSharding(mesh, P(*parts))
    return jax.tree_util.tree_map_with_path(spec, abstract_batch)


def cache_shardings(abstract_cache, cfg: ModelConfig, mesh):
    """KV caches / SSD states (stacked layouts with leading layer dims)."""
    dp = dp_axes(mesh)

    def spec(path, leaf):
        keys = [str(getattr(k, "key", "")) for k in path]
        shape = leaf.shape
        last = keys[-1] if keys else ""
        if last == "len":
            return NamedSharding(mesh, P(*([None] * (leaf.ndim - 1)),
                                         _fit(mesh, shape[-1], dp)))
        if last in ("k", "v", "ck", "cv"):          # (..., B, S, KVH, hd)
            lead = leaf.ndim - 4
            b, s, kvh = shape[lead], shape[lead + 1], shape[lead + 2]
            head_ax = _fit(mesh, kvh, "model")
            seq_ax = None if head_ax else _fit(mesh, s, "model")
            return NamedSharding(mesh, P(*([None] * lead),
                                         _fit(mesh, b, dp), seq_ax,
                                         head_ax, None))
        if last in ("c_kv", "k_rope"):              # (L, B, S, r)
            return NamedSharding(mesh, P(
                None, _fit(mesh, shape[1], dp), None,
                _fit(mesh, shape[-1], "model")))
        if last == "h":                             # (L, B, H, N, P)
            return NamedSharding(mesh, P(
                None, _fit(mesh, shape[1], dp),
                _fit(mesh, shape[2], "model"), None, None))
        if last == "conv":                          # (L, B, K-1, C)
            return NamedSharding(mesh, P(
                None, _fit(mesh, shape[1], dp), None,
                _fit(mesh, shape[-1], "model")))
        parts = [None] * leaf.ndim
        return NamedSharding(mesh, P(*parts))
    return jax.tree_util.tree_map_with_path(spec, abstract_cache)


def replicated(mesh):
    return NamedSharding(mesh, P())
