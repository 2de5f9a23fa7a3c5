"""Transformer building blocks (pure-functional JAX).

Conventions:
  * params are nested dicts of fp32 arrays; compute casts to cfg dtype,
  * every function takes (params, inputs, cfg) and is shard_map/pjit
    agnostic — sharding is applied by launch/sharding.py constraints,
  * attention is q-chunked (flash-style memory behaviour without a custom
    kernel) for long-context prefill; decode uses a kv-chunked formulation
    whose chunk axis is shardable across the model axis (sequence-parallel
    cache reads).
"""
from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AnalogMode, ModelConfig, resolve_analog_mode
from repro.core import AdcConfig
from repro.core.adc import quantize_dequantize  # noqa: F401  (re-export)
from repro.core.tiled_analog import (analog_project, analog_project_batched,
                                     crossbar_from_model,
                                     is_analog_container, program_stacked,
                                     readout)
from repro.kernels.ops import _adc_fake_quant as _kernels_adc_fake_quant
from repro.kernels.ops import fakequant_project

Array = jax.Array

# Number of kv chunks used by the sequence-parallel decode attention; must
# be divisible by the model-axis size (16 in production, 1 in tests).
DECODE_KV_CHUNKS = 16
# Query chunk for flash-style prefill attention.
Q_CHUNK = 512


def cdtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------
# Activation-sharding hints.  XLA's SPMD propagation loses the batch
# sharding inside long scans; drivers install a context (mesh + DP axes)
# before tracing and the stacks re-constrain activations at block
# boundaries.  No-op when no context is installed (tests, single device).
# The context itself lives in ``core.shardctx`` so the crossbar sim can
# consult the same mesh (sharded analog training); these re-exports keep
# the historical import site working.
# --------------------------------------------------------------------------

from repro.core.shardctx import (clear_shard_context,  # noqa: F401
                                 get_shard_context, set_shard_context)


def shard_batch_dim(x: Array) -> Array:
    """Constrain dim0 (batch) to the data-parallel axes.

    A context with ``dp_axes=None`` (the sharded *analog* step, which keeps
    the batch replicated and parallelises over the container tile grid) is
    a no-op here.

    K5 (perf): REPRO_SEQ_SHARD=1 additionally shards the sequence dim over
    the model axis at block boundaries (Megatron-SP): the TP boundary then
    carries reduce-scatter + all-gather instead of all-reduce — half the
    link bytes — and norms/elementwise run on 1/TP of the tokens."""
    import os
    mesh, dp, tp = get_shard_context()
    if mesh is None or dp is None or x.ndim < 2:
        return x
    size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        size *= mesh.shape[a]
    if x.shape[0] % size != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    rest = [None] * (x.ndim - 1)
    if (os.environ.get("REPRO_SEQ_SHARD") and x.ndim >= 3
            and x.shape[1] % mesh.shape[tp] == 0):
        rest[0] = tp
    spec = P(dp, *rest)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Initialisers
# --------------------------------------------------------------------------

def dense_init(key: Array, d_in: int, d_out: int) -> Array:
    scale = 1.0 / np.sqrt(d_in)
    return scale * jax.random.truncated_normal(
        key, -2.0, 2.0, (d_in, d_out), dtype=jnp.float32)


def embed_init(key: Array, vocab: int, d: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d),
                                       dtype=jnp.float32)


def proj_from_weights(w: Array, cfg: ModelConfig) -> dict:
    """Wrap explicit weights as projection params (digital dict, or the
    weights programmed onto a tiled-crossbar container in device mode).
    Stacked weights — e.g. an (E, K, N) expert stack — program one tile
    grid (and one calibration) per matrix."""
    if resolve_analog_mode(cfg) is AnalogMode.DEVICE:
        return program_stacked(w, crossbar_from_model(cfg))
    return {"w": w}


def proj_init(key: Array, d_in: int, d_out: int, cfg: ModelConfig) -> dict:
    """Projection parameters: a digital weight dict, or — in analog device
    mode — the weights programmed onto a tiled-crossbar container."""
    return proj_from_weights(dense_init(key, d_in, d_out), cfg)


def proj_readout(p: dict, cfg: ModelConfig) -> dict:
    """Digital serial read of a projection back to a weight dict."""
    if is_analog_container(p):
        return {"w": readout(p, crossbar_from_model(cfg))}
    return p


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}


def rmsnorm(p: dict, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * p["scale"]).astype(dt)


# --------------------------------------------------------------------------
# Analog-aware projection
# --------------------------------------------------------------------------

def project(p: dict, x: Array, cfg: ModelConfig) -> Array:
    """Linear layer; in analog mode the matmul carries the crossbar I/O
    fake-quantisation (per-token input DAC + per-K-tile output ADC),
    keeping the HLO a single fused matmul + cheap elementwise epilogues.

    In analog *device* mode (``AnalogMode.DEVICE``) the params are a
    tiled-crossbar container and the matmul executes on the simulated
    array: forward=VMM, backward=MVM through the same conductances, with
    the quantised update operands taped for the in-situ optimizer
    (core/tiled_analog.py).  Fake-quant mode keeps QAT semantics: a fused
    digital matmul with crossbar I/O quantisation epilogues.
    """
    if is_analog_container(p):
        return analog_project(p, x, crossbar_from_model(cfg))
    w = p["w"].astype(x.dtype)
    if resolve_analog_mode(cfg) is AnalogMode.DIGITAL:
        return x @ w
    adc = AdcConfig(in_bits=cfg.analog_in_bits,
                    out_bits=cfg.analog_out_bits)
    y = fakequant_project(x.astype(jnp.float32), w.astype(jnp.float32),
                          adc, cfg.analog_rows,
                          impl=getattr(cfg, "analog_read_impl", None))
    return y.astype(x.dtype)


def expert_project(p, x: Array, cfg: ModelConfig,
                   rows: Optional[Array] = None) -> Array:
    """Expert-batched linear layer: x (E, T, K) -> (E, T, N).

    ``rows`` (E,) optionally gives each expert's filled rows, the first of
    its T (the rest are zero): the crossbar kernels then read and write
    only those.

    ``p`` is either a raw (E, K, N) weight stack (digital / fakequant MoE)
    or an expert-batched tiled-crossbar container (device mode) — each
    expert's matrix lives on its own tile grid, read/written with the
    expert dim riding the layer-batched kernel grid
    (core/analog_registry).

    In fakequant mode the per-expert matmuls carry the same crossbar I/O
    fake-quantisation as :func:`project` (per-token input DAC, per-K-tile
    output ADC), vmapped over the expert dim — QAT semantics now cover
    the MoE expert einsums, not just the dense projections.
    """
    if is_analog_container(p):
        return analog_project_batched(p, x, crossbar_from_model(cfg),
                                      rows=rows)
    if resolve_analog_mode(cfg) is AnalogMode.DIGITAL:
        return jnp.einsum("etk,ekn->etn", x, p.astype(x.dtype))
    adc = AdcConfig(in_bits=cfg.analog_in_bits,
                    out_bits=cfg.analog_out_bits)
    # Keep the differentiable jnp path: QAT trains through the fake-quant
    # graph, and a Pallas read has no batching rule under this vmap.
    impl = getattr(cfg, "analog_read_impl", None)
    if impl not in (None, "auto", "jnp", "chain"):
        impl = "jnp"
    y = jax.vmap(lambda xe, we: fakequant_project(
        xe, we, adc, cfg.analog_rows, impl=impl))(
            x.astype(jnp.float32), p.astype(jnp.float32))
    return y.astype(x.dtype)


# Fake-quant math lives with the kernels now (kernels/ops.fakequant_project
# owns both the differentiable jnp path and the fused Pallas kernel); the
# historical name is kept as an alias for external callers.
_adc_fake_quant = _kernels_adc_fake_quant


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, cfg: ModelConfig) -> Array:
    """YaRN inverse frequencies (DeepSeek-V2's rotary embedding): the
    interpolated ``1 / (factor * theta**(2i/dim))`` and the extrapolated
    ``1 / theta**(2i/dim)``, blended by a linear ramp over the dims
    between the ``beta_fast`` and ``beta_slow`` rotation counts of the
    original context (extrapolated below the ramp, interpolated above)."""
    base, factor = cfg.rope_theta, cfg.yarn_factor
    ctx = cfg.yarn_original_max_pos

    def dim_of(rotations):
        return dim * math.log(ctx / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(dim, base)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_table(dim: int, cfg: ModelConfig) -> Tuple[Array, float]:
    """(inverse frequencies, cos/sin magnitude) of the config's rope."""
    if cfg.yarn_factor > 1.0:
        mag = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        return yarn_freqs(dim, cfg), mag
    return rope_freqs(dim, cfg.rope_theta), 1.0


def apply_rope(x: Array, positions: Array, theta: float,
               freqs: Optional[Array] = None, mag: float = 1.0) -> Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  ``freqs``
    replaces the plain table of ``theta`` (YaRN, :func:`rope_table`) and
    ``mag`` scales cos and sin."""
    dt = x.dtype
    dim = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dim, theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (.., s, d/2)
    cos = mag * jnp.cos(angles)[..., :, None, :]
    sin = mag * jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(dt)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def attn_init(key: Array, cfg: ModelConfig, d_in: Optional[int] = None,
              fused: bool = True) -> dict:
    """Attention projections.

    ``fused=True`` (the default) lays q/k/v out on ONE column-concatenated
    projection ``wqkv`` — the same init draws as the unfused layout,
    stacked side by side.  One matmul (one crossbar VMM sweep, one MVM
    backward, one wide rank-k parallel write) drives all three heads'
    worth of columns; on the simulated hardware this is exactly a wider
    array sharing the same row drives.  Cross-attention (q from x, k/v
    from another stream of the same width) uses the same wide array: both
    token streams drive it in a single application and each stream keeps
    its own column block (see ``attention``).  ``fused=False`` keeps the
    legacy split layout (one container per projection).
    """
    d = d_in or cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    wo = proj_init(ks[3], cfg.n_heads * hd, cfg.d_model, cfg)
    if not fused:
        return {
            "wq": proj_init(ks[0], d, cfg.n_heads * hd, cfg),
            "wk": proj_init(ks[1], d, cfg.n_kv_heads * hd, cfg),
            "wv": proj_init(ks[2], d, cfg.n_kv_heads * hd, cfg),
            "wo": wo,
        }
    w = jnp.concatenate(
        [dense_init(ks[0], d, cfg.n_heads * hd),
         dense_init(ks[1], d, cfg.n_kv_heads * hd),
         dense_init(ks[2], d, cfg.n_kv_heads * hd)], axis=1)
    return {"wqkv": proj_from_weights(w, cfg), "wo": wo}


def _split_heads(x: Array, n: int) -> Array:
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


def _chunked_sdpa(q: Array, k: Array, v: Array, causal: bool,
                  q_offset: int = 0, scale: Optional[float] = None
                  ) -> Array:
    """Softmax attention, scanning over query chunks.

    q: (B, Sq, H, hd);  k/v: (B, Skv, KVH, hd).  GQA folds the head group
    into the einsum.  Peak memory ~ B * H * Q_CHUNK * Skv.
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = scale or 1.0 / np.sqrt(hd)
    qg = q.reshape(b, sq, kvh, group, hd)

    n_chunks = max(1, sq // Q_CHUNK) if sq % Q_CHUNK == 0 else 1
    qc = qg.reshape(b, n_chunks, sq // n_chunks, kvh, group, hd)
    kv_pos = jnp.arange(skv)

    def chunk(carry, xs):
        qi, idx = xs
        # (b, cq, kvh, g, skv)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            q_pos = q_offset + idx * (sq // n_chunks) \
                + jnp.arange(sq // n_chunks)
            mask = kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
        return carry, o

    _, out = jax.lax.scan(
        chunk, None, (jnp.moveaxis(qc, 1, 0), jnp.arange(n_chunks)))
    # output head dim follows V (MLA uses asymmetric qk / v dims)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, v.shape[-1])
    return out.astype(q.dtype)


def _decode_sdpa(q: Array, k: Array, v: Array, kv_len: Array,
                 scale: Optional[float] = None) -> Array:
    """Single-token attention against a (possibly sequence-sharded) cache.

    q: (B, 1, H, hd); k/v: (B, S, KVH, hd).  The cache sequence is viewed as
    DECODE_KV_CHUNKS chunks; per-chunk partial softmax stats combine exactly
    (flash-decoding) so the chunk axis can shard across the model axis.
    """
    b, _, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = scale or 1.0 / np.sqrt(hd)
    c = DECODE_KV_CHUNKS if s % DECODE_KV_CHUNKS == 0 else 1
    sl = s // c
    kc = k.reshape(b, c, sl, kvh, hd)
    vc = v.reshape(b, c, sl, kvh, v.shape[-1])
    qg = q.reshape(b, kvh, group, hd)
    scores = jnp.einsum("bkgd,bcskd->bckgs", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
    pos = jnp.arange(s).reshape(c, sl)
    valid = pos[None, :, :] < kv_len[:, None, None]          # (b, c, sl)
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    m_c = jnp.max(scores, axis=-1)                            # (b,c,kvh,g)
    l_c = jnp.sum(jnp.exp(scores - m_c[..., None]), axis=-1)
    o_c = jnp.einsum("bckgs,bcskd->bckgd",
                     jnp.exp(scores - m_c[..., None]),
                     vc.astype(jnp.float32))
    m = jnp.max(m_c, axis=1, keepdims=True)                  # (b,1,kvh,g)
    w = jnp.exp(m_c - m) * l_c                               # (b,c,kvh,g)
    o = jnp.sum(o_c * jnp.exp(m_c - m)[..., None], axis=1) \
        / jnp.maximum(jnp.sum(w, axis=1), 1e-30)[..., None]
    return o.reshape(b, 1, h, v.shape[-1]).astype(q.dtype)


def _cached_sdpa(q: Array, k: Array, v: Array, q_pos: Array,
                 scale: Optional[float] = None) -> Array:
    """Chunk attention against a partially-filled cache (chunked prefill).

    q: (B, Sq, H, hd); k/v: (B, S, KVH, hd) — the full cache after this
    chunk was written; q_pos: (B, Sq) absolute positions of the queries.
    Cache slot s is visible to the query at position p iff s <= p: causal
    within the chunk, and slots beyond the filled prefix are masked out
    because their index exceeds every query position.
    """
    b, sq, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = scale or 1.0 / np.sqrt(hd)
    qg = q.reshape(b, sq, kvh, group, hd)
    scores = jnp.einsum("bqkgd,bskd->bqkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, None, :] <= q_pos[:, :, None]   # (b, sq, s)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def attention(p: dict, x: Array, cfg: ModelConfig, *, causal: bool = True,
              positions: Optional[Array] = None,
              cache: Optional[dict] = None,
              x_kv: Optional[Array] = None,
              use_rope: bool = True) -> Tuple[Array, Optional[dict]]:
    """Self- or cross-attention with optional KV cache.

    cache = {"k": (B, S, KVH, hd), "v": ..., "len": (B,)} — decode appends
    at position ``len`` and attends to the full cache.  Append mode also
    covers chunked prefill (sq > 1 with explicit ``positions``): the chunk
    is written at ``len`` and attends causally to the filled prefix.  A
    cache with ``positions=None`` and sq > 1 is a fresh full prefill.
    """
    with jax.named_scope("attention"):
        hd = cfg.resolved_head_dim
        b, sq = x.shape[0], x.shape[1]
        append = cache is not None and x_kv is None and (
            sq == 1 or positions is not None)
        if "wqkv" in p:  # fused projection (one VMM sweep)
            nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
            if x_kv is None:
                qkv = project(p["wqkv"], x, cfg)
                q = _split_heads(qkv[..., :nq], cfg.n_heads)
                k_self = _split_heads(qkv[..., nq:nq + nkv], cfg.n_kv_heads)
                v_self = _split_heads(qkv[..., nq + nkv:], cfg.n_kv_heads)
            else:
                # Fused cross-attention: ONE wide array serves q (driven by
                # the x stream) and k/v (driven by the x_kv stream).  Both
                # streams go through in a single application — concatenated
                # along tokens — so the taped backward deposits one operand
                # block per step (a container must not be applied twice); the
                # unused column blocks of each stream carry zero cotangents
                # and add nothing to the rank-k write.
                both = jnp.concatenate([x, x_kv.astype(x.dtype)], axis=1)
                qkv = project(p["wqkv"], both, cfg)
                q = _split_heads(qkv[:, :sq, :nq], cfg.n_heads)
                k_self = _split_heads(qkv[:, sq:, nq:nq + nkv],
                                      cfg.n_kv_heads)
                v_self = _split_heads(qkv[:, sq:, nq + nkv:], cfg.n_kv_heads)
        else:
            q = _split_heads(project(p["wq"], x, cfg), cfg.n_heads)
            k_self = v_self = None
        kv_src = x if x_kv is None else x_kv
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
        if append:
            # --- decode / chunked prefill: append sq tokens to the cache ----
            k_new = k_self if k_self is not None else _split_heads(
                project(p["wk"], x, cfg), cfg.n_kv_heads)
            v_new = v_self if v_self is not None else _split_heads(
                project(p["wv"], x, cfg), cfg.n_kv_heads)
            if use_rope:
                q = apply_rope(q, positions, cfg.rope_theta)
                k_new = apply_rope(k_new, positions, cfg.rope_theta)
            idx = cache["len"]  # (B,)
            k = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n, (i, 0, 0)))(cache["k"], k_new.astype(cache["k"].dtype),
                                  idx)
            v = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n, (i, 0, 0)))(cache["v"], v_new.astype(cache["v"].dtype),
                                  idx)
            if sq == 1:
                o = _decode_sdpa(q, k, v, idx + 1)
            else:
                o = _cached_sdpa(q, k, v, positions)
            new_cache = {"k": k, "v": v, "len": idx + sq}
        else:
            if k_self is not None:
                k, v = k_self, v_self
            else:
                k = _split_heads(project(p["wk"], kv_src, cfg),
                                 cfg.n_kv_heads)
                v = _split_heads(project(p["wv"], kv_src, cfg),
                                 cfg.n_kv_heads)
            if use_rope and x_kv is None:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            o = _chunked_sdpa(q, k, v, causal=causal and x_kv is None)
            new_cache = None
            if cache is not None and x_kv is None:
                # prefill fills the cache
                pad = cache["k"].shape[1] - k.shape[1]
                new_cache = {
                    "k": jnp.pad(k.astype(cache["k"].dtype),
                                 ((0, 0), (0, pad), (0, 0), (0, 0))),
                    "v": jnp.pad(v.astype(cache["v"].dtype),
                                 ((0, 0), (0, pad), (0, 0), (0, 0))),
                    "len": jnp.full((b,), k.shape[1], dtype=jnp.int32),
                }
        out = project(p["wo"], o.reshape(b, sq, -1), cfg)
        return out, new_cache


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               d_kv: Optional[int] = None) -> dict:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd),
                       dtype=cdtype(cfg)),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd),
                       dtype=cdtype(cfg)),
        "len": jnp.zeros((batch,), dtype=jnp.int32),
    }


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# --------------------------------------------------------------------------

def mla_init(key: Array, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": proj_init(ks[0], d, cfg.n_heads * qk_dim, cfg),
        "wkv_a": proj_init(ks[1], d,
                           cfg.kv_lora_rank + cfg.qk_rope_dim, cfg),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank),
        "wkv_b": proj_init(
            ks[2], cfg.kv_lora_rank,
            cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim), cfg),
        "wo": proj_init(ks[3], cfg.n_heads * cfg.v_head_dim, d, cfg),
    }


def mla_attention(p: dict, x: Array, cfg: ModelConfig, *,
                  positions: Optional[Array] = None,
                  cache: Optional[dict] = None
                  ) -> Tuple[Array, Optional[dict]]:
    """Multi-head latent attention.  The cache stores the compressed
    latent (kv_lora_rank) + shared rope key — MLA's memory saving.
    Append mode (decode, or chunked prefill when ``positions`` is given)
    writes at the cached ``len``; see ``attention``.  With YaRN rope the
    softmax scale carries the squared temperature factor ``mscale``
    (DeepSeek-V2: ``mscale(factor, mscale_all_dim)**2 / sqrt(qk_dim)``)."""
    with jax.named_scope("attention"):
        return _mla_attention(p, x, cfg, positions, cache)


def _mla_attention(p: dict, x: Array, cfg: ModelConfig, positions,
                   cache) -> Tuple[Array, Optional[dict]]:
    b, sq, d = x.shape
    h = cfg.n_heads
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    append = cache is not None and (sq == 1 or positions is not None)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    freqs, mag = rope_table(cfg.qk_rope_dim, cfg)
    scale = 1.0 / np.sqrt(qk_dim)
    if cfg.yarn_factor > 1.0 and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    q = _split_heads(project(p["wq"], x, cfg), h)  # (b,s,h,qk_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, freqs, mag)

    kv_a = project(p["wkv_a"], x, cfg)
    c_kv, k_rope = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        freqs, mag)  # single shared rope head

    if append:
        idx = cache["len"]
        c_all = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i, 0)))(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                           idx)
        kr_all = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i, 0)))(cache["k_rope"],
                           k_rope[:, :, 0, :].astype(cache["k_rope"].dtype),
                           idx)
        new_cache = {"c_kv": c_all, "k_rope": kr_all, "len": idx + sq}
        kv_len = idx + sq
    else:
        c_all, kr_all = c_kv, k_rope[:, :, 0, :]
        new_cache = None
        if cache is not None:
            pad = cache["c_kv"].shape[1] - sq
            new_cache = {
                "c_kv": jnp.pad(c_all.astype(cache["c_kv"].dtype),
                                ((0, 0), (0, pad), (0, 0))),
                "k_rope": jnp.pad(kr_all.astype(cache["k_rope"].dtype),
                                  ((0, 0), (0, pad), (0, 0))),
                "len": jnp.full((b,), sq, dtype=jnp.int32),
            }
        kv_len = None

    if cache is not None and sq == 1 and "w" in p["wkv_b"] \
            and os.environ.get("REPRO_MLA_ABSORB"):
        # K8 (perf, beyond-paper): absorbed MLA decode (DeepSeek-V2 §2.1.2).
        # Fold wkv_b's K-block into the query and its V-block into the
        # output so attention runs in the latent space — O(B·H·S·r) per
        # step instead of re-expanding per-head K/V over the whole cache,
        # O(B·S·r·H·(dn+dv)): a (dn+dv) ≈ 256x FLOP cut at 32k context.
        r = cfg.kv_lora_rank
        dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
        wkv = p["wkv_b"]["w"].astype(jnp.float32).reshape(r, h, dn + dv)
        wkb, wvb = wkv[..., :dn], wkv[..., dn:]
        q_abs = jnp.einsum("bhd,rhd->bhr",
                           q_nope[:, 0].astype(jnp.float32), wkb)
        c32 = c_all.astype(jnp.float32)
        scores = (jnp.einsum("bhr,btr->bht", q_abs, c32)
                  + jnp.einsum("bhd,btd->bht",
                               q_rope[:, 0].astype(jnp.float32),
                               kr_all.astype(jnp.float32))) * scale
        valid = jnp.arange(c_all.shape[1])[None, :] < kv_len[:, None]
        scores = jnp.where(valid[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bht,btr->bhr", probs, c32)
        o = jnp.einsum("bhr,rhd->bhd", ctx, wvb)[:, None].astype(x.dtype)
        out = project(p["wo"], o.reshape(b, sq, -1), cfg)
        return out, new_cache

    # expand latent to per-head keys/values
    kv = project(p["wkv_b"], c_all.astype(x.dtype), cfg)
    kv = kv.reshape(b, -1, h, cfg.qk_nope_dim + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_dim], axis=-1)
    k_rope_b = jnp.broadcast_to(kr_all[:, :, None, :].astype(x.dtype),
                                (b, k_nope.shape[1], h, cfg.qk_rope_dim))
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    if append and sq == 1:
        o = _decode_sdpa(q_full, k_full, v, kv_len, scale=scale)
    elif append:
        o = _cached_sdpa(q_full, k_full, v, positions, scale=scale)
    else:
        o = _chunked_sdpa(q_full, k_full, v, causal=True, scale=scale)
    out = project(p["wo"], o.reshape(b, sq, -1), cfg)
    return out, new_cache


def make_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank),
                          dtype=cdtype(cfg)),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim),
                            dtype=cdtype(cfg)),
        "len": jnp.zeros((batch,), dtype=jnp.int32),
    }


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def ffn_init(key: Array, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    """Gated FFNs lay up+gate out on one column-concatenated projection
    ``w_upgate`` (same init draws as the split layout): both halves share
    the row drives, so the analog forward/backward/update each run as one
    sweep of a double-width array."""
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.gated:
        w = jnp.concatenate([dense_init(ks[0], d, ff),
                             dense_init(ks[2], d, ff)], axis=1)
        return {"w_upgate": proj_from_weights(w, cfg),
                "w_down": proj_init(ks[1], ff, d, cfg)}
    return {"w_up": proj_init(ks[0], d, ff, cfg),
            "w_down": proj_init(ks[1], ff, d, cfg)}


def ffn(p: dict, x: Array, cfg: ModelConfig) -> Array:
    act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
    if "w_upgate" in p:
        up, gate = jnp.split(project(p["w_upgate"], x, cfg), 2, axis=-1)
        up = act(gate) * up
    elif cfg.gated:
        up = act(project(p["w_gate"], x, cfg)) * project(p["w_up"], x, cfg)
    else:
        up = act(project(p["w_up"], x, cfg))
    return project(p["w_down"], up, cfg)
