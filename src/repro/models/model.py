"""Unified model API over every architecture family.

    params                 = init_params(key, cfg)
    loss, aux              = loss_fn(params, batch, cfg)
    logits, cache          = prefill(params, batch, cfg)
    logits, cache          = decode_step(params, cache, tokens, cfg)
    cache                  = init_cache(cfg, batch, max_len)
    batch                  = input_specs(cfg, shape)   # ShapeDtypeStructs

``batch`` dicts: {"tokens", "labels"} plus modality stubs
({"vision": (B, n_vis, d)} / {"audio": (B, T_a, d)}) per DESIGN.md — the
frontends are stubs that supply precomputed patch/frame embeddings.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec

from . import transformer as tf
from .layers import cdtype, make_cache, make_mla_cache, proj_readout
from .ssm import make_ssm_state
from repro.core.tiled_analog import is_analog_container

Array = jax.Array


# --------------------------------------------------------------------------
# init / forward
# --------------------------------------------------------------------------

def init_params(key: Array, cfg: ModelConfig) -> dict:
    if cfg.family == "vlm":
        return tf.vlm_init(key, cfg)
    if cfg.family == "audio":
        return tf.audio_init(key, cfg)
    if cfg.family in ("ssm", "hybrid"):
        return tf.ssm_stack_init(key, cfg)
    return tf.decoder_init(key, cfg)


def readout_digital(params, cfg: ModelConfig, path=()):
    """Serial read of an analog-device model back to digital weights.

    Walks the parameter tree and converts every tiled-crossbar container
    back to its digital layout — a plain ``{"w": (g - ref) / w_scale}``
    dict for projections, the raw (E, K, N) weight stack for expert-
    batched containers (the registry decides which is which) — so the
    same checkpoint can be evaluated (or fine-tuned) with
    ``cfg.digital()``.  A no-op on digital trees.

    Since the serve backend reads conductances in-array
    (``repro.serve.make_engine(..., backend="analog")``), this is a
    convenience wrapper for digital eval/fine-tune flows, not the only
    exit path from device state.  :func:`program_digital` is its
    inverse.
    """
    from repro.core.analog_registry import EXPERT_BATCHED, classify
    if is_analog_container(params):
        rd = proj_readout(params, cfg)
        return rd["w"] if classify(path) == EXPERT_BATCHED else rd
    if isinstance(params, dict):
        return {k: readout_digital(v, cfg, path + (k,))
                for k, v in params.items()}
    return params


def program_digital(params, cfg: ModelConfig, path=()):
    """Inverse of :func:`readout_digital`: program a digital tree's
    projections onto tiled-crossbar containers.

    Registry-driven walk: ``{"w": ...}`` projection dicts and raw
    expert/SSM weight stacks whose path the registry classifies as a
    crossbar consumer are programmed with ``program_stacked`` under
    ``cfg``'s device model; digital-core matrices (embeddings, router,
    norms, ...) pass through untouched.  ``cfg`` must resolve to device
    mode.  Round-trips: ``readout_digital(program_digital(w)) == w`` up
    to float error, because ``program_linear``'s default scale
    (8x the weight RMS) is deterministic in the weights and leaves
    clipping headroom.
    """
    from repro.core.analog_registry import KINDS, classify_param
    from repro.core.tiled_analog import (crossbar_from_model,
                                         program_stacked)
    if cfg.resolved_analog_mode.value != "device":
        raise ValueError(
            "program_digital needs a device-mode config (analog=True, "
            f"analog_mode='device'); got {cfg.resolved_analog_mode.value!r}")
    if isinstance(params, dict):
        if set(params) == {"w"} and classify_param(path) in KINDS:
            return program_stacked(params["w"], crossbar_from_model(cfg))
        return {k: program_digital(v, cfg, path + (k,))
                for k, v in params.items()}
    if getattr(params, "ndim", 0) >= 2 and classify_param(path) in KINDS:
        return program_stacked(params, crossbar_from_model(cfg))
    return params


def forward(params: dict, batch: Dict[str, Array], cfg: ModelConfig,
            caches=None, shared_caches=None, positions=None,
            stats: bool = False):
    """Returns (logits, new_caches, new_shared_caches, aux).  ``aux`` is
    the scalar auxiliary loss, or with ``stats`` an MoE stack's routing
    stats (``moe.moe_layer``) where the model has them."""
    tokens = batch["tokens"]
    if cfg.family == "vlm":
        logits, nc, aux = tf.vlm_apply(params, tokens, batch["vision"],
                                       cfg, caches=caches,
                                       positions=positions)
        return logits, nc, None, aux
    if cfg.family == "audio":
        # decode steps (one token, caches carry cross-KV) skip the encoder
        if caches is not None and tokens.shape[1] == 1:
            enc = None
        else:
            enc = tf.audio_encode(params, batch["audio"], cfg)
        logits, nc, aux = tf.audio_decode(params, tokens, enc, cfg,
                                          caches=caches,
                                          positions=positions)
        return logits, nc, None, aux
    if cfg.family in ("ssm", "hybrid"):
        logits, ns, nsh, aux = tf.ssm_stack_apply(
            params, tokens, cfg, states=caches,
            shared_caches=shared_caches, positions=positions)
        return logits, ns, nsh, aux
    logits, nc, aux = tf.decoder_apply(params, tokens, cfg, caches=caches,
                                       positions=positions)
    if isinstance(aux, dict) and not stats:
        aux = aux["aux"]
    return logits, nc, None, aux


def loss_fn(params: dict, batch: Dict[str, Array], cfg: ModelConfig
            ) -> Tuple[Array, Dict[str, Array]]:
    logits, _, _, aux = forward(params, batch, cfg, stats=True)
    labels = batch["labels"]
    # Sharding-friendly CE: one-hot contraction instead of take_along_axis
    # (a gather over the vocab-sharded dim would force an all-gather of the
    # full logits tensor).
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true_logit = jnp.sum(
            logits * jax.nn.one_hot(labels, cfg.vocab, dtype=jnp.float32),
            axis=-1)
        loss = jnp.mean(lse - true_logit)
    # An MoE stack's aux is its routing stats: the balance loss and the
    # dispatch counters (``moe.moe_apply``), which ride out as metrics.
    stats = dict(aux) if isinstance(aux, dict) else {"aux": aux}
    aux = stats.pop("aux")
    total = loss + 0.01 * aux
    return total, {"ce": loss, "aux": aux, **stats}


# --------------------------------------------------------------------------
# caches / serving
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Returns (caches, shared_caches) in the stacked layout each family's
    scan expects."""
    def stack(make, n):
        one = make()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), one)

    if cfg.family == "vlm":
        g = cfg.cross_attn_every
        n_groups = cfg.n_layers // g
        inner = g - 1
        one = make_cache(cfg, batch, max_len)
        caches = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None, None],
                                       (n_groups, inner, *a.shape)), one)
        return caches, None
    if cfg.family == "audio":
        hd = cfg.resolved_head_dim

        def make_audio():
            return {"self": make_cache(cfg, batch, max_len),
                    "ck": jnp.zeros((batch, cfg.n_audio_frames,
                                     cfg.n_kv_heads, hd), cdtype(cfg)),
                    "cv": jnp.zeros((batch, cfg.n_audio_frames,
                                     cfg.n_kv_heads, hd), cdtype(cfg))}
        return stack(make_audio, cfg.n_layers), None
    if cfg.family == "ssm":
        return stack(lambda: make_ssm_state(cfg, batch), cfg.n_layers), None
    if cfg.family == "hybrid":
        states = stack(lambda: make_ssm_state(cfg, batch), cfg.n_layers)
        n_groups = cfg.n_layers // cfg.attn_every
        shared = stack(lambda: make_cache(cfg, batch, max_len), n_groups)
        return states, shared
    if cfg.use_mla:
        return stack(lambda: make_mla_cache(cfg, batch, max_len),
                     cfg.n_layers), None
    return stack(lambda: make_cache(cfg, batch, max_len),
                 cfg.n_layers), None


def prefill(params: dict, batch: Dict[str, Array], cfg: ModelConfig,
            max_len: int):
    """Run the prompt through the model, returning last-token logits and a
    cache sized ``max_len``."""
    b, s = batch["tokens"].shape
    caches, shared = init_cache(cfg, b, max_len)
    logits, nc, nsh, _ = forward(params, batch, cfg, caches=caches,
                                 shared_caches=shared)
    return logits[:, -1], (nc, nsh)


def decode_step(params: dict, cache, tokens: Array, cfg: ModelConfig,
                batch_extras: Optional[Dict[str, Array]] = None):
    """One decode step.  tokens: (B,) int32.  Returns (logits, new_cache)."""
    caches, shared = cache
    # position = current cache length (uniform across batch by construction)
    positions = None
    lens = _cache_lens(cache, cfg)
    if lens is not None:
        positions = lens[:, None]
    batch = {"tokens": tokens[:, None]}
    if batch_extras:
        batch.update(batch_extras)
    logits, nc, nsh, _ = forward(params, batch, cfg, caches=caches,
                                 shared_caches=shared, positions=positions)
    return logits[:, -1], (nc, nsh)


def prefill_chunk(params: dict, cache, tokens: Array, cfg: ModelConfig,
                  batch_extras: Optional[Dict[str, Array]] = None):
    """Append a chunk of prompt tokens to an existing cache.

    tokens: (B, S).  Each row's chunk is written at its current cache
    length and attends causally to the filled prefix, so long prompts can
    be prefilled in fixed-shape chunks interleaved with decode steps.
    Returns (full-chunk logits (B, S, V), new_cache); rows advance by S —
    callers padding the final chunk fix the lengths with
    ``cache_with_lens``.  Requires a family with a positional KV cache
    (dense / moe); SSM-state families need exact-length prefill.
    """
    caches, shared = cache
    lens = _cache_lens(cache, cfg)
    if lens is None:
        raise ValueError(
            f"family {cfg.family!r} has no positional cache; "
            "chunked prefill is unsupported — use prefill()")
    positions = lens[:, None] + jnp.arange(tokens.shape[1])[None, :]
    batch = {"tokens": tokens}
    if batch_extras:
        batch.update(batch_extras)
    logits, nc, nsh, _ = forward(params, batch, cfg, caches=caches,
                                 shared_caches=shared, positions=positions)
    return logits, (nc, nsh)


def cache_lens(cache, cfg: ModelConfig) -> Optional[Array]:
    """Per-row filled lengths of a cache, or None for positionless
    (pure-SSM) families."""
    return _cache_lens(cache, cfg)


def cache_with_lens(cache, lens: Array):
    """Return ``cache`` with every per-row length leaf set to ``lens`` (B,).

    Length leaves are the ``"len"`` entries of the cache dicts (stacked as
    (..., B), batch-last), so a (B,) vector broadcasts onto each of them.
    """
    def fix(path, leaf):
        if path and isinstance(path[-1], jax.tree_util.DictKey) \
                and path[-1].key == "len":
            return jnp.broadcast_to(lens.astype(leaf.dtype), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def cache_batch_axes(cfg: ModelConfig, max_len: int):
    """Pytree (matching the cache structure) of each leaf's batch-dim index.

    The stacked cache layouts put the batch dim at a different axis per
    family/leaf ((L, B, ...), (n_groups, inner, B, ...), ...); comparing
    abstract shapes at two batch sizes finds it without hard-coding
    layouts.  Used by the slot-insert/reset surgery below.
    """
    a = jax.eval_shape(lambda: init_cache(cfg, 2, max_len))
    b = jax.eval_shape(lambda: init_cache(cfg, 3, max_len))

    def axis_of(x, y):
        for i, (p, q) in enumerate(zip(x.shape, y.shape)):
            if p != q:
                return i
        raise ValueError(f"no batch dim found in cache leaf {x.shape}")
    return jax.tree.map(axis_of, a, b)


def cache_insert(dst, src, slot, axes):
    """Write the rows of ``src`` (a cache built with a smaller batch) into
    ``dst`` starting at batch row ``slot``.  ``axes`` comes from
    ``cache_batch_axes``; ``slot`` may be a traced scalar, so a jitted
    insert compiles once per engine configuration."""
    return jax.tree.map(
        lambda d, s, ax: jax.lax.dynamic_update_slice_in_dim(
            d, s.astype(d.dtype), slot, axis=ax),
        dst, src, axes)


def cache_reset_row(cache, slot, axes):
    """Zero batch row ``slot`` of a cache (eviction hygiene: a freed slot
    holds no stale K/V and its length is 0 so nothing attends to it)."""
    return jax.tree.map(
        lambda d, ax: jax.lax.dynamic_update_slice_in_dim(
            d, jnp.zeros_like(
                jax.lax.dynamic_slice_in_dim(d, 0, 1, axis=ax)),
            slot, axis=ax),
        cache, axes)


def _cache_lens(cache, cfg: ModelConfig) -> Optional[Array]:
    caches, shared = cache
    if cfg.family in ("ssm",):
        return None  # positionless (no rope in SSD path)
    if cfg.family == "hybrid":
        return shared["len"][0] if shared is not None else None
    if cfg.family == "vlm":
        return caches["len"][0, 0]
    if cfg.family == "audio":
        return caches["self"]["len"][0]
    return caches["len"][0]


# --------------------------------------------------------------------------
# Abstract input specs for the dry-run (no allocation)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a given cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": sds((b, s), i32)}
        if shape.kind == "train":
            batch["labels"] = sds((b, s), i32)
        if cfg.family == "vlm":
            batch["vision"] = sds((b, cfg.n_vision_tokens, cfg.d_model),
                                  jnp.bfloat16)
        if cfg.family == "audio":
            batch["audio"] = sds((b, cfg.n_audio_frames, cfg.d_model),
                                 jnp.bfloat16)
        return batch
    # decode: one token against a cache of size seq_len
    batch = {"tokens": sds((b,), i32)}
    if cfg.family == "vlm":
        batch["vision"] = sds((b, cfg.n_vision_tokens, cfg.d_model),
                              jnp.bfloat16)
    if cfg.family == "audio":
        batch["audio"] = sds((b, cfg.n_audio_frames, cfg.d_model),
                             jnp.bfloat16)
    return batch


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs of the cache pytree (eval_shape, no allocation)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
