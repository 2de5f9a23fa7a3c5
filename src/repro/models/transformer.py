"""Layer stacks for every assigned architecture family.

All stacks scan over stacked per-layer parameters (compact HLO at 100
layers, natural remat boundary).  Heterogeneous patterns map onto grouped
scans:

  dense / moe : scan over N identical blocks
  vlm         : scan over groups of [cross-attn block + G self blocks]
  audio       : encoder scan + decoder scan (self + cross per layer)
  ssm         : scan over SSD blocks
  hybrid      : scan over groups of [K ssm blocks] + shared attn block
                (single weight set applied at every group boundary)

Modes: ``train`` (full seq, logits), ``prefill`` (full seq, logits + cache),
``decode`` (one token, cache update).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.tiled_analog import pop_tapes, push_tapes

from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (
    _chunked_sdpa, _split_heads, attention, attn_init, cdtype, dense_init,
    embed_init, ffn, ffn_init, mla_attention, mla_init, proj_init, project,
    rmsnorm, rmsnorm_init, shard_batch_dim)

Array = jax.Array

import os


def _remat(f):
    """Remat policy knob (perf iteration K1, EXPERIMENTS.md §Perf):
    REPRO_REMAT=dots saves matmul outputs instead of recomputing the whole
    block body — fewer replayed FLOPs *and* fewer replayed TP collectives
    at the cost of activation memory.  REPRO_REMAT=none disables remat
    entirely: the right call for smoke-scale models and CPU benchmarking,
    where activation memory is free and the recompute chain only inflates
    compile time and step latency (the analog sim chain especially — its
    per-projection quantise/saturate/ADC ops all replay under remat)."""
    pol = os.environ.get("REPRO_REMAT", "full")
    if pol == "none":
        return f
    if pol == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(f)


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------

def _self_attn_init(key: Array, cfg: ModelConfig) -> dict:
    return mla_init(key, cfg) if cfg.use_mla else attn_init(key, cfg)


def _self_attn(p: dict, x: Array, cfg: ModelConfig, positions, cache):
    if cfg.use_mla:
        return mla_attention(p, x, cfg, positions=positions, cache=cache)
    return attention(p, x, cfg, positions=positions, cache=cache)


def dense_block_init(key: Array, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model),
            "attn": _self_attn_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "ffn": ffn_init(k2, cfg)}


def dense_block(p: dict, x: Array, cfg: ModelConfig, positions, cache):
    x = shard_batch_dim(x)
    h, new_cache = _self_attn(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                              cfg, positions, cache)
    x = x + h
    x = x + ffn(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    return x, new_cache, jnp.zeros((), jnp.float32)


def moe_block_init(key: Array, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model),
            "attn": _self_attn_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "moe": moe_mod.moe_init(k2, cfg)}


def moe_block(p: dict, x: Array, cfg: ModelConfig, positions, cache):
    """Returns (x, cache, routing stats): the aux loss and the dispatch
    counters of ``moe.moe_layer``."""
    x = shard_batch_dim(x)
    h, new_cache = _self_attn(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                              cfg, positions, cache)
    x = x + h
    y, stats = moe_mod.moe_layer(p["moe"],
                                 rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    return x + y, new_cache, stats


def cross_block_init(key: Array, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    # xattn uses the fused wqkv layout: one wide array driven by both
    # token streams in a single application (layers.attention) — the last
    # per-projection sim chains are gone.
    return {"ln1": rmsnorm_init(cfg.d_model),
            "xattn": attn_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "ffn": ffn_init(k2, cfg),
            "gate_attn": jnp.zeros((), jnp.float32),
            "gate_ffn": jnp.zeros((), jnp.float32)}


def cross_block(p: dict, x: Array, kv: Array, cfg: ModelConfig):
    """Gated cross-attention block (llama-3.2-vision style)."""
    x = shard_batch_dim(x)
    h, _ = attention(p["xattn"], rmsnorm(p["ln1"], x, cfg.norm_eps), cfg,
                     causal=False, x_kv=kv, use_rope=False)
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
    h = ffn(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * h


def ssm_block_init(key: Array, cfg: ModelConfig) -> dict:
    return {"ln": rmsnorm_init(cfg.d_model),
            "ssm": ssm_mod.ssm_init(key, cfg)}


def ssm_block(p: dict, x: Array, cfg: ModelConfig, state):
    x = shard_batch_dim(x)
    h, new_state = ssm_mod.ssm_apply(p["ssm"],
                                     rmsnorm(p["ln"], x, cfg.norm_eps),
                                     cfg, state=state)
    return x + h, new_state


# --------------------------------------------------------------------------
# Stacked scans
# --------------------------------------------------------------------------

def _stack_init(key: Array, n: int, init_fn) -> dict:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _scan_blocks(params, x, body, caches=None, length=None, aux0=None):
    """Scan ``body`` over stacked layer params (+ optional stacked caches).

    body(layer_params, x, cache) -> (x, new_cache, aux).  ``aux`` is a
    scalar summed over the layers, or a dict shaped like ``aux0`` whose
    ``max_*`` entries take the layers' maximum and the rest their sum.
    """
    if aux0 is None:
        aux0 = jnp.zeros((), jnp.float32)

    def combine(acc, aux):
        if not isinstance(acc, dict):
            return acc + aux
        return {k: jnp.maximum(v, aux[k]) if k.startswith("max_")
                else v + aux[k] for k, v in acc.items()}

    def f(carry, xs):
        lp, cache = xs
        x, aux_acc = carry
        with jax.named_scope("layer"):
            x, new_cache, aux = body(lp, x, cache)
            return (x, combine(aux_acc, aux)), new_cache

    # The scan's own work (slicing and stacking the per-layer trees) is
    # ``layer_scan``; what a layer computes is ``layer`` or a scope
    # inside it.
    xs = (params, caches)
    with jax.named_scope("layer_scan"):
        (x, aux), new_caches = jax.lax.scan(
            _remat(f), (x, aux0), xs, length=length)
    return x, new_caches, aux


# --------------------------------------------------------------------------
# Decoder-only models (dense / moe families)
# --------------------------------------------------------------------------

def decoder_init(key: Array, cfg: ModelConfig) -> dict:
    """Embedding, the stacked layers and the head.  An MoE model's
    ``n_dense_layers`` leading dense layers are a stack of their own,
    ``dense_layers``, scanned before the expert layers of ``layers``."""
    ks = jax.random.split(key, 4)
    block_init = moe_block_init if cfg.n_experts else dense_block_init
    p = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "layers": _stack_init(ks[1], cfg.n_layers - cfg.n_dense_layers,
                              partial(block_init, cfg=cfg)),
        "final_ln": rmsnorm_init(cfg.d_model),
    }
    if cfg.n_dense_layers:
        p["dense_layers"] = _stack_init(ks[3], cfg.n_dense_layers,
                                        partial(dense_block_init, cfg=cfg))
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": dense_init(ks[2], cfg.d_model, cfg.vocab)}
    return p


def _logits(p: dict, x: Array, cfg: ModelConfig) -> Array:
    with jax.named_scope("head_loss"):
        x = rmsnorm(p["final_ln"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            # scale keeps init logits O(1) (embeddings are unit-variance)
            return x.astype(jnp.float32) @ p["embed"].T \
                / (cfg.d_model ** 0.5)
        return (x @ p["lm_head"]["w"].astype(x.dtype)).astype(jnp.float32)


def _embed_lookup(p: dict, tokens: Array, cfg: ModelConfig) -> Array:
    """K3 (perf): casting the table to bf16 *before* the gather makes the
    vocab-sharded gather's combine collective run at 2 bytes/elem."""
    with jax.named_scope("head_loss"):
        if os.environ.get("REPRO_EMBED_BF16"):
            return p["embed"].astype(cdtype(cfg))[tokens]
        return p["embed"][tokens].astype(cdtype(cfg))


def decoder_apply(p: dict, tokens: Array, cfg: ModelConfig, *,
                  caches=None, positions=None
                  ) -> Tuple[Array, Any, Array]:
    """Logits, new caches and the aux of the layer stack (for an MoE
    model the routing stats of ``moe.moe_layer``, combined over layers)."""
    x = _embed_lookup(p, tokens, cfg)
    n_dense = cfg.n_dense_layers
    dense_caches = None
    if n_dense:
        if caches is not None:
            dense_caches = jax.tree.map(lambda a: a[:n_dense], caches)
            caches = jax.tree.map(lambda a: a[n_dense:], caches)
        body = lambda lp, h, c: dense_block(lp, h, cfg, positions, c)
        x, dense_caches, _ = _scan_blocks(p["dense_layers"], x, body,
                                          dense_caches, length=n_dense)
    if cfg.n_experts:
        block, aux0 = moe_block, moe_mod.stats_zero()
    else:
        block, aux0 = dense_block, None
    body = lambda lp, h, c: block(lp, h, cfg, positions, c)
    x, new_caches, aux = _scan_blocks(p["layers"], x, body, caches,
                                     length=cfg.n_layers - n_dense,
                                     aux0=aux0)
    if dense_caches is not None and new_caches is not None:
        new_caches = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                  dense_caches, new_caches)
    return _logits(p, x, cfg), new_caches, aux


# --------------------------------------------------------------------------
# VLM: grouped scan  [cross + G self] x n_groups   (llama-3.2-vision)
# --------------------------------------------------------------------------

def vlm_init(key: Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    g = cfg.cross_attn_every
    n_groups = cfg.n_layers // g
    n_self = n_groups * (g - 1)
    p = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "self_layers": _stack_init(
            ks[1], n_self, partial(dense_block_init, cfg=cfg)),
        "cross_layers": _stack_init(
            ks[2], n_groups, partial(cross_block_init, cfg=cfg)),
        "final_ln": rmsnorm_init(cfg.d_model),
        "lm_head": {"w": dense_init(ks[3], cfg.d_model, cfg.vocab)},
    }
    return p


def vlm_apply(p: dict, tokens: Array, vision: Array, cfg: ModelConfig, *,
              caches=None, positions=None) -> Tuple[Array, Any, Array]:
    """vision: (B, n_vision_tokens, d_model) from the stub frontend."""
    x = _embed_lookup(p, tokens, cfg)
    vision = vision.astype(cdtype(cfg))
    g = cfg.cross_attn_every
    n_groups = cfg.n_layers // g
    inner = g - 1
    self_params = jax.tree.map(
        lambda a: a.reshape(n_groups, inner, *a.shape[1:]),
        p["self_layers"])
    self_caches = caches

    def group(carry, xs):
        x = carry
        cp, sp, cache_g = xs
        x = cross_block(cp, x, vision, cfg)

        def inner_body(h, inner_xs):
            lp, c = inner_xs
            h, nc, _ = dense_block(lp, h, cfg, positions, c)
            return h, nc

        x, new_cache_g = jax.lax.scan(_remat(inner_body), x,
                                      (sp, cache_g))
        return x, new_cache_g

    x, new_caches = jax.lax.scan(group, x,
                                 (p["cross_layers"], self_params,
                                  self_caches))
    return _logits(p, x, cfg), new_caches, jnp.zeros((), jnp.float32)


# --------------------------------------------------------------------------
# Audio enc-dec (whisper)
# --------------------------------------------------------------------------

def audio_init(key: Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)

    def enc_block_init(k):
        return dense_block_init(k, cfg)

    def dec_block_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": rmsnorm_init(cfg.d_model),
                "attn": attn_init(k1, cfg),
                "lnx": rmsnorm_init(cfg.d_model),
                "xattn": attn_init(k2, cfg),  # fused wqkv cross-attention
                "ln2": rmsnorm_init(cfg.d_model),
                "ffn": ffn_init(k3, cfg)}

    return {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "enc_pos": 0.02 * jax.random.normal(
            ks[1], (cfg.n_audio_frames, cfg.d_model), dtype=jnp.float32),
        "enc_layers": _stack_init(ks[2], cfg.n_encoder_layers,
                                  enc_block_init),
        "enc_ln": rmsnorm_init(cfg.d_model),
        "dec_layers": _stack_init(ks[3], cfg.n_layers, dec_block_init),
        "final_ln": rmsnorm_init(cfg.d_model),
        "lm_head": {"w": dense_init(ks[4], cfg.d_model, cfg.vocab)},
    }


def audio_encode(p: dict, frames: Array, cfg: ModelConfig) -> Array:
    """frames: (B, T_audio, d_model) — stub conv-frontend output."""
    x = frames.astype(cdtype(cfg)) + p["enc_pos"].astype(cdtype(cfg))

    def body(lp, h, c):
        h = shard_batch_dim(h)
        h1, _ = attention(lp["attn"], rmsnorm(lp["ln1"], h, cfg.norm_eps),
                          cfg, causal=False, use_rope=False)
        h = h + h1
        h = h + ffn(lp["ffn"], rmsnorm(lp["ln2"], h, cfg.norm_eps), cfg)
        return h, None, jnp.zeros((), jnp.float32)

    x, _, _ = _scan_blocks(p["enc_layers"], x, body,
                           length=cfg.n_encoder_layers)
    return rmsnorm(p["enc_ln"], x, cfg.norm_eps)


def audio_decode(p: dict, tokens: Array, enc, cfg: ModelConfig, *,
                 caches=None, positions=None) -> Tuple[Array, Any, Array]:
    """Decoder stack.  Cross-attention K/V over the encoder output are
    computed once (prefill) and cached per layer — decode steps never touch
    the encoder (enc=None then; see model.forward)."""
    x = _embed_lookup(p, tokens, cfg)

    def body(lp, h, c):
        h = shard_batch_dim(h)
        self_c = c["self"] if c is not None else None
        h1, nc_self = attention(lp["attn"],
                                rmsnorm(lp["ln1"], h, cfg.norm_eps),
                                cfg, positions=positions, cache=self_c)
        h = h + h1
        # cross-attention with cached K/V
        hn = rmsnorm(lp["lnx"], h, cfg.norm_eps)
        xp = lp["xattn"]
        hd = cfg.resolved_head_dim
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        if "wqkv" in xp:
            # Fused cross-attention: the decoder stream and (at prefill)
            # the encoder stream drive ONE wide array in a single
            # application; decode steps project only the decoder token
            # and read K/V from the cache.
            if enc is None:
                ck, cv = c["ck"].astype(h.dtype), c["cv"].astype(h.dtype)
                q = _split_heads(project(xp["wqkv"], hn, cfg)[..., :nq],
                                 cfg.n_heads)
            else:
                both = jnp.concatenate([hn, enc.astype(hn.dtype)], axis=1)
                qkv = project(xp["wqkv"], both, cfg)
                sq = hn.shape[1]
                q = _split_heads(qkv[:, :sq, :nq], cfg.n_heads)
                ck = _split_heads(qkv[:, sq:, nq:nq + nkv],
                                  cfg.n_kv_heads)
                cv = _split_heads(qkv[:, sq:, nq + nkv:], cfg.n_kv_heads)
        else:  # legacy split layout
            if enc is None:
                ck, cv = c["ck"].astype(h.dtype), c["cv"].astype(h.dtype)
            else:
                ck = _split_heads(project(xp["wk"], enc, cfg),
                                  cfg.n_kv_heads)
                cv = _split_heads(project(xp["wv"], enc, cfg),
                                  cfg.n_kv_heads)
            q = _split_heads(project(xp["wq"], hn, cfg), cfg.n_heads)
        o = _chunked_sdpa(q, ck, cv, causal=False)
        h = h + project(xp["wo"], o.reshape(*h.shape[:-1], -1), cfg)
        h = h + ffn(lp["ffn"], rmsnorm(lp["ln2"], h, cfg.norm_eps), cfg)
        new_c = None
        if c is not None:
            new_c = {"self": nc_self,
                     "ck": ck.astype(c["ck"].dtype),
                     "cv": cv.astype(c["cv"].dtype)}
        return h, new_c, jnp.zeros((), jnp.float32)

    x, new_caches, aux = _scan_blocks(p["dec_layers"], x, body, caches,
                                      length=cfg.n_layers)
    return _logits(p, x, cfg), new_caches, aux


# --------------------------------------------------------------------------
# SSM / hybrid
# --------------------------------------------------------------------------

def ssm_stack_init(key: Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "layers": _stack_init(ks[1], cfg.n_layers,
                              partial(ssm_block_init, cfg=cfg)),
        "final_ln": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": dense_init(ks[2], cfg.d_model, cfg.vocab)}
    if cfg.attn_every:  # zamba2 shared attention block
        kk = jax.random.split(ks[3], 3)
        p["shared_in"] = proj_init(kk[0], 2 * cfg.d_model, cfg.d_model,
                                   cfg)
        p["shared_ln"] = rmsnorm_init(cfg.d_model)
        p["shared_ln2"] = rmsnorm_init(cfg.d_model)
        p["shared_attn"] = attn_init(kk[1], cfg)
        p["shared_ffn"] = ffn_init(kk[2], cfg)
    return p


def ssm_stack_apply(p: dict, tokens: Array, cfg: ModelConfig, *,
                    states=None, shared_caches=None, positions=None
                    ) -> Tuple[Array, Any, Any, Array]:
    x0 = _embed_lookup(p, tokens, cfg)
    x = x0

    def body(lp, h, st):
        h, new_st = ssm_block(lp, h, cfg, st)
        return h, new_st, jnp.zeros((), jnp.float32)

    if not cfg.attn_every:
        x, new_states, aux = _scan_blocks(p["layers"], x, body, states,
                                          length=cfg.n_layers)
        return _logits(p, x, cfg), new_states, None, aux

    # hybrid: groups of K ssm layers + shared attention block
    k = cfg.attn_every
    n_groups = cfg.n_layers // k
    trailing = cfg.n_layers - n_groups * k
    grouped = jax.tree.map(
        lambda a: a[: n_groups * k].reshape(n_groups, k, *a.shape[1:]),
        p["layers"])
    tail = jax.tree.map(lambda a: a[n_groups * k:], p["layers"])
    if states is not None:
        g_states = jax.tree.map(
            lambda a: a[: n_groups * k].reshape(n_groups, k, *a.shape[1:]),
            states)
        t_states = jax.tree.map(lambda a: a[n_groups * k:], states)
    else:
        g_states = t_states = None

    # The shared block is ONE weight set applied at every group boundary.
    # Its analog containers therefore tape per *application*: the train
    # step allocates tapes with a leading (n_groups,) dim
    # (core/analog_registry.tape_reps), which we peel off here and scan
    # over, so each group boundary deposits its own write-driver operands
    # (summed outer products over applications = the rank-k write a
    # reused array receives).  Inference / digital trees carry no tapes
    # and take the plain path.
    shared_p = {"in": p["shared_in"], "attn": p["shared_attn"],
                "ffn": p["shared_ffn"]}
    shared_clean, shared_tapes, has_tapes = pop_tapes(shared_p)

    def shared_block(h, cache, tp=None):
        sp = shared_clean if tp is None else push_tapes(shared_clean, tp)
        h = shard_batch_dim(h)
        inp = jnp.concatenate([h, x0], axis=-1)
        h_in = project(sp["in"], inp, cfg)
        h1, new_cache = attention(
            sp["attn"], rmsnorm(p["shared_ln"], h_in, cfg.norm_eps),
            cfg, positions=positions, cache=cache)
        h = h + h1
        h = h + ffn(sp["ffn"],
                    rmsnorm(p["shared_ln2"], h, cfg.norm_eps), cfg)
        return h, new_cache

    def group(carry, xs):
        h = carry
        gp, gs, sc = xs[:3]
        tp = xs[3] if len(xs) > 3 else None

        def inner(hh, ixs):
            lp, st = ixs
            hh, new_st = ssm_block(lp, hh, cfg, st)
            return hh, new_st

        h, new_gs = jax.lax.scan(_remat(inner), h, (gp, gs))
        h, new_sc = shared_block(h, sc, tp)
        return h, (new_gs, new_sc)

    xs = (grouped, g_states, shared_caches)
    if has_tapes:
        xs = xs + (shared_tapes,)
    x, (new_g_states, new_shared) = jax.lax.scan(group, x, xs)

    def inner(hh, ixs):
        lp, st = ixs
        hh, new_st = ssm_block(lp, hh, cfg, st)
        return hh, new_st

    x, new_t_states = jax.lax.scan(_remat(inner), x,
                                   (tail, t_states), length=trailing)

    new_states = None
    if states is not None:
        # restore the flat (n_layers, ...) stacked layout
        new_states = jax.tree.map(
            lambda a, b: jnp.concatenate(
                [a.reshape(n_groups * k, *a.shape[2:]), b], axis=0),
            new_g_states, new_t_states)
    return _logits(p, x, cfg), new_states, new_shared, \
        jnp.zeros((), jnp.float32)
