"""Pallas TPU kernel: fused analog crossbar read (VMM and transpose MVM).

One kernel now performs the paper's *entire* read pipeline per physical
tile — the chain the simulator used to emit as separate XLA ops
(quantise → tiled matmul → clip/round ADC → rescale) is fused so the
quantisation boundary lives inside the tile loop, exactly where the
hardware has it (DESIGN.md §2):

  * leading edge — DAC temporal coding: the raw float activations ride in
    and are quantised in-kernel against the per-matrix full scale
    (``adc.quantize_input`` semantics; the one remaining leading-edge
    reduction, ``max |x|``, is computed outside and rides in as a scalar),
  * per tile — the differential-pair subtract ``G - G_ref`` happens on the
    VMEM-resident blocks (no dense (K, N) difference is ever materialised
    in HBM), followed by the MXU matmul of one ``rows x cols`` crossbar
    tile and the integrator-saturation + ramp-ADC epilogue at the tile
    boundary,
  * across reduction tiles — digital accumulation in the output block,
  * trailing edge — the final ``x_scale / w_scale`` rescale on the last
    reduction step, while the block is still in VMEM.

Grid layout
-----------
VMM:  ``(L, B/blk_b, N/cols, K/rows)`` — reduction innermost so the output
block stays resident while partial ADC results accumulate.  MVM (transpose
read: drive columns, integrate rows) swaps the roles of K and N and
contracts the *column* dimension of the same stored G tile, so no
materialised transpose exists: ``(L, B/blk_b, K/rows, N/cols)``.

``L`` is a leading *lead-dims* grid axis mirroring ``xbar_update.py``: one
``pallas_call`` sweeps a scan-stacked ``(L, K, N)`` container, and richer
lead shapes — the expert-batched ``(L, E, K, N)`` MoE stacks — are
flattened onto the same axis (``core/analog_registry.flatten_lead`` order),
so the read of layers x experts is still one launch.  Per-matrix scalars
ride in whole as an ``(L, 2)`` SMEM operand ``[x_scale, x_scale /
w_scale]``, indexed by the lead grid coordinate inside the body.

Inside one grid step the body walks its batch block in ``READ_STRIP``
token strips, in two passes: integrate each strip's charge into a VMEM
scratch while accumulating the tile's range statistics, then convert
the scratch through the ADC into the resident output block.  Mosaic
unrolls a whole-block matmul, so the strips keep the compiled body (and
its compile time) independent of the token count.

VMEM at 1024x1024 tiles and a 2048-token block (f32): x 8 MiB + out
8 MiB, each double-buffered, + G and G_ref 4 MiB each, double-buffered,
+ 8 MiB charge scratch + the difference tile ≈ 68 MiB with slack
(:func:`_read_vmem_bytes`); each call sets ``vmem_limit_bytes`` to its
estimate and refuses a block over ``VMEM_LIMIT_CAP``.

Execution paths (``impl``)
--------------------------
``"pallas"`` compiles with Mosaic (TPU); ``"interpret"`` runs the same
kernel under the Pallas interpreter (the validation path on any backend
— bit-checked against ``core.xbar_ops._tiled_read`` on the operand
classes where bitwise equality is well defined, see below);  ``"jnp"``
runs :func:`_tiled_read_twin`, a fused jnp twin that keeps the chain's
exact einsum/reduction structure (including the exact-reduce sharding
pins) while collapsing single-reduction-tile reads to one flat MXU
dot — the fast path on hosts without Mosaic.  ``"auto"`` picks
``"pallas"`` on TPU (meshless) and ``"jnp"`` everywhere else; a Mosaic
kernel cannot express the exact-reduce pins, so an active mesh context
always resolves to ``"jnp"``.  ``"chain"`` names the pre-fusion
op-by-op path that still lives in ``core.xbar_ops`` (kept for
benchmarking and as the parity oracle); it is resolved by the callers
there and never dispatches into this module.

Bit-parity contract
-------------------
Bitwise equality between *structurally different* f32 programs is not
controllable on XLA CPU: the backend contracts mul+add chains into FMA
(skipping the product's intermediate rounding) per-lowering, strips
``+0.0`` / double-bitcast / f32 ``reduce_precision`` identities, and
folds compile-time-constant scale factors forward through runtime
multiplies.  The enforced contract is therefore:

  * twin vs chain — bit-identical whenever the twin takes the einsum
    path (structurally the same program), eager-vs-eager or
    jit-vs-jit.  The production same-seed contract (sharded ==
    unsharded conductances) compares twin vs twin and is exact
    unconditionally.
  * interpret kernel vs chain — bit-identical in ``fixed`` range mode
    with a power-of-two ADC lsb (arbitrary float data, ragged edge
    tiles, multi-tile grids, both read directions): the saturation
    bound is a compile-time constant, every ADC output is an exact
    integer multiple of a power of two, and all partial sums are exact,
    so neither FMA contraction nor reduction-order choices can move a
    bit.  This class exercises every fused stage end to end and is the
    CI bit-check.  In ``dynamic`` range mode the saturation bound
    itself is a data-dependent float reduction (``sumsq`` over the
    calibration block) whose lowering differs between the kernel body
    and the chain's 4-D reduce — bitwise equality across those two
    programs is not well defined; agreement is ~1-2 ulp, bounded by
    FMA contraction of ``code * lsb + acc`` and one rounding of the
    range calibration.

Dynamic ADC range: one integrator range is calibrated per (tile, batch
block), so the calibration population matches the reference exactly when
``block_b >= B`` (the default).  Zero-padded token rows integrate zero
charge, which the nonzero-count statistics ignore.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.adc import (_clip, _round, adc_quantize,
                            integrator_range, integrator_saturation,
                            quantize_input)
from repro.core.crossbar import CrossbarConfig, pad_to_tiles
from repro.core.shardctx import (ShardMeta, combine_partials_exact,
                                 current_mesh, replicate_for_exact_reduce,
                                 shard_index)

Array = jax.Array

READ_IMPLS = ("auto", "pallas", "interpret", "jnp", "chain")
# Scoped-VMEM ceiling for one kernel: v5e and v6e have 128 MiB of VMEM
# per core; the rest stays with the compiler.
VMEM_LIMIT_CAP = 100 * 2**20
# Every f32 contraction of the read and the write runs at full f32
# precision on every backend: the TPU's default would round the
# conductance operand to bfloat16, which the kernel (Mosaic) never does.
_HIGHEST = jax.lax.Precision.HIGHEST


def resolve_read_impl(impl: Optional[str] = None) -> str:
    """Resolve the read execution path (see module docstring).

    ``None``/``"auto"``: ``"jnp"`` under an active mesh context of more
    than one device (the twin carries the exact-reduce pins; a compiled
    kernel cannot), else ``"pallas"`` on TPU and ``"jnp"`` everywhere
    else.  A one-device mesh has nothing to pin, so it keeps the kernel.
    """
    if impl in (None, "auto"):
        mesh = current_mesh()
        if mesh is not None and mesh.size > 1:
            return "jnp"
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in READ_IMPLS:
        raise ValueError(f"impl must be one of {READ_IMPLS}")
    return impl


def _charge_stats(q: Array) -> tuple:
    """(sum of squares, nonzero count) of a block of column charge — the
    statistics ``core.adc.integrator_range`` turns into a tile's range."""
    return jnp.sum(jnp.square(q)), jnp.sum((q != 0).astype(jnp.float32))


def _adc_epilogue(q: Array, sat, cfg: CrossbarConfig) -> Array:
    """Integrator saturation + ramp-ADC quantisation of a tile's charge
    against its range ``sat`` — literally the clip of
    ``core.adc.integrator_saturation`` followed by ``adc_quantize``."""
    return adc_quantize(_clip(q, -sat, sat), sat, cfg.adc)


# --------------------------------------------------------------------------
# The fused kernel
# --------------------------------------------------------------------------

# Tokens per pass of the kernel body's inner loops.  The body walks its
# batch block in strips so the compiled code stays the size of one strip
# (Mosaic unrolls a whole-block matmul), whatever the token count.
READ_STRIP = 256


def _read_kernel(x_ref, g_ref, r_ref, sc_ref, o_ref, q_ref, *,
                 cfg: CrossbarConfig, transpose: bool, n_steps: int,
                 strip: int):
    """One (matrix, batch block, output tile, reduction tile) grid step.

    VMM contracts the drive rows of the stored tile; MVM (``transpose``:
    drive columns, integrate rows) contracts its columns, so no
    transposed copy exists.  The tile's integrator range is shared by the
    whole batch block, so the body makes two passes over its token
    strips: the first integrates each strip's charge into the ``q_ref``
    scratch and accumulates the range statistics, the second converts it
    through the ADC into the resident output block.
    """
    # Grid coordinates and the per-matrix scalars are read at the body's
    # top level: inside a pl.when branch they would land in a cond jaxpr
    # the interpreter cannot lower.
    lid, step = pl.program_id(0), pl.program_id(3)
    x_scale, out_scale = sc_ref[lid, 0], sc_ref[lid, 1]
    n_rows = cfg.cols if transpose else cfg.rows
    contract = ((1,), (1,)) if transpose else ((1,), (0,))
    levels = float(cfg.adc.in_levels)

    @pl.when(step == 0)
    def _init():
        o_ref[0, :, :] = jnp.zeros_like(o_ref[0, :, :])

    # Differential pair: the reference column subtracts in-array (VMEM).
    diff = g_ref[0, :, :] - r_ref[0, :, :]

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * strip, strip), strip)

    def integrate(i, stats):
        ts = rows_of(i)
        # Leading edge: DAC temporal coding against the per-matrix scale.
        xi = _clip(_round(x_ref[0, ts, :] / x_scale, None), -levels, levels)
        q = jax.lax.dot_general(xi, diff, (contract, ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)
        q_ref[ts, :] = q
        sumsq, nz = _charge_stats(q)
        return stats[0] + sumsq, stats[1] + nz

    n_strips = q_ref.shape[0] // strip
    zero = jnp.zeros((), jnp.float32)
    sumsq, nz = jax.lax.fori_loop(0, n_strips, integrate, (zero, zero))
    sat = integrator_range(sumsq, nz, cfg.adc, n_rows, cfg.device.gmax)

    def convert(i, carry):
        ts = rows_of(i)
        o_ref[0, ts, :] += _adc_epilogue(q_ref[ts, :], sat, cfg)
        return carry

    jax.lax.fori_loop(0, n_strips, convert, 0)

    @pl.when(step == n_steps - 1)
    def _rescale():
        # Trailing edge: the digital x_scale / w_scale rescale, applied
        # while the accumulated block is still resident.
        o_ref[0, :, :] = o_ref[0, :, :] * out_scale


def _read_vmem_bytes(b: int, cfg: CrossbarConfig) -> int:
    """Scoped VMEM the fused read needs for a ``b``-token block: the
    double-buffered x / G / G_ref / out blocks, the charge scratch, the
    difference tile, strip temporaries and 4 MiB of slack for Mosaic's
    own scratch."""
    tile = cfg.rows * cfg.cols
    wide = max(cfg.rows, cfg.cols)
    cells = (2 * (b * (cfg.rows + cfg.cols) + 2 * tile) + b * wide + tile
             + 4 * READ_STRIP * wide)
    return 4 * cells + (4 << 20)


def _pallas_read(x: Array, g: Array, ref: Array, sc: Array,
                 cfg: CrossbarConfig, transpose: bool,
                 block_b: Optional[int], interpret: bool) -> Array:
    """Launch the fused kernel over lead-flattened (L, ...) operands."""
    lyr, b = x.shape[0], x.shape[1]
    k, n = g.shape[1], g.shape[2]
    bb = block_b or b
    # Zero token rows integrate zero charge, which the range statistics
    # ignore, so a block padded to whole strips reads as the batch alone.
    strip = min(READ_STRIP, -(-bb // 8) * 8)
    bb = -(-bb // strip) * strip
    vmem = _read_vmem_bytes(bb, cfg)
    if vmem > VMEM_LIMIT_CAP:
        # The dynamic ADC range is calibrated over one batch block, and
        # matches the reference only when that block is the whole batch:
        # a larger batch needs the range computed in a separate pass.
        raise ValueError(
            f"fused read of a {bb}-token block at {cfg.rows}x{cfg.cols} "
            f"tiles needs {vmem / 2**20:.0f} MiB of VMEM, over the "
            f"{VMEM_LIMIT_CAP / 2**20:.0f} MiB cap; read in smaller "
            f"token batches")
    drive, out_w = (cfg.cols, cfg.rows) if transpose else (cfg.rows,
                                                           cfg.cols)
    x = jnp.pad(x, ((0, 0), (0, (-b) % bb), (0, (-x.shape[2]) % drive)))
    gp = jnp.pad(g, ((0, 0), (0, (-k) % cfg.rows), (0, (-n) % cfg.cols)))
    rp = jnp.pad(ref, ((0, 0), (0, (-k) % cfg.rows), (0, (-n) % cfg.cols)))
    _, kp, np_ = gp.shape
    bp = x.shape[1]
    # Grid (L, B/bb, out tiles, reduction tiles): the reduction runs
    # innermost so the output block stays resident while partial ADC
    # results accumulate.  G / G_ref tiles index as (k-tile, n-tile)
    # whichever way the array is driven.
    if transpose:
        grid = (lyr, bp // bb, kp // cfg.rows, np_ // cfg.cols)
        g_index = lambda l_, b_, o_, r_: (l_, o_, r_)
        out_shape, out_dim = (lyr, bp, kp), k
    else:
        grid = (lyr, bp // bb, np_ // cfg.cols, kp // cfg.rows)
        g_index = lambda l_, b_, o_, r_: (l_, r_, o_)
        out_shape, out_dim = (lyr, bp, np_), n
    g_spec = pl.BlockSpec((1, cfg.rows, cfg.cols), g_index)
    out = pl.pallas_call(
        functools.partial(_read_kernel, cfg=cfg, transpose=transpose,
                          n_steps=grid[3], strip=strip),
        grid=grid,
        # The (L, 2) per-matrix scalars sit whole in SMEM, indexed by the
        # lead grid coordinate inside the body.
        in_specs=[pl.BlockSpec((1, bb, drive),
                               lambda l_, b_, o_, r_: (l_, b_, r_)),
                  g_spec, g_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, bb, out_w),
                               lambda l_, b_, o_, r_: (l_, b_, o_)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, out_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x, gp, rp, sc)
    return out[:, :b, :out_dim]


# --------------------------------------------------------------------------
# Fused fakequant projection (QAT read: digital weights, crossbar I/O)
# --------------------------------------------------------------------------

def _fakequant_kernel(x_ref, w_ref, sc_ref, o_ref, *, adc, n_ksteps: int):
    """One (token-block, k-tile) step of the fakequant read.

    Same leading/trailing structure as the device kernel, but the weights
    are digital (no reference subtract, no conductance units) and the ADC
    fake-quant range is per *token*: ``models/layers._adc_fake_quant``
    calibrates on the RMS of each token's tile partial over the full
    output width — hence the weight block spans all N columns.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[:, :] = jnp.zeros_like(o_ref)

    in_lv = float(adc.in_levels)
    out_lv = float(adc.out_levels)
    sc = sc_ref[0, 0]
    # DAC round-trip (quantize_dequantize): the dequantised activations
    # drive the digital matmul.
    xq = _clip(_round(x_ref[:, :] / sc, None), -in_lv, in_lv) * sc
    q = jnp.dot(xq, w_ref[:, :], preferred_element_type=jnp.float32)
    sat = adc.sat_sigmas * jnp.sqrt(
        jnp.mean(jnp.square(q), axis=-1, keepdims=True) + 1e-12)
    lsb = sat / out_lv
    o_ref[:, :] += _clip(_round(q / lsb, None), -out_lv, out_lv) * lsb


def fakequant_read_pallas(x: Array, w: Array, adc, rows: int,
                          block_t: Optional[int] = None,
                          interpret: bool = False) -> Array:
    """Fused fakequant projection: x (T, K) f32, w (K, N) f32 -> (T, N).

    Forward-only (a Pallas call carries no VJP) — the QAT training path
    stays on the jnp twin in ``kernels.ops.fakequant_project``; this
    kernel serves inference.  Grid ``(T/blk_t, K/rows)`` with the
    reduction innermost; per-token ADC ranges make the N axis untiled.
    """
    t, k = x.shape
    n = w.shape[1]
    bt = min(block_t or 128, t)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / adc.in_levels
    sc = jnp.reshape(scale.astype(jnp.float32), (1, 1))
    xp = jnp.pad(x, ((0, (-t) % bt), (0, (-k) % rows)))
    wp = jnp.pad(w, ((0, (-k) % rows), (0, 0)))
    grid = (xp.shape[0] // bt, xp.shape[1] // rows)
    out = pl.pallas_call(
        functools.partial(_fakequant_kernel, adc=adc, n_ksteps=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((bt, rows), lambda t_, k_: (t_, k_)),
                  pl.BlockSpec((rows, n), lambda t_, k_: (k_, 0)),
                  pl.BlockSpec((1, 1), lambda t_, k_: (0, 0))],
        out_specs=pl.BlockSpec((bt, n), lambda t_, k_: (t_, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], n), jnp.float32),
        interpret=interpret,
    )(xp, wp, sc)
    return out[:t]


# --------------------------------------------------------------------------
# The fused jnp twin
# --------------------------------------------------------------------------

def _tiled_read_twin(x_int: Array, diff: Array, cfg: CrossbarConfig,
                     transpose: bool) -> Array:
    """Bit-exact twin of ``core.xbar_ops._tiled_read``.

    Same per-tile einsum, same saturation/ADC reduce axes, same
    exact-reduce sharding pin — plus a single-reduction-tile fast path:
    when the whole reduction fits one physical tile the 4-D tile einsum
    collapses to one flat MXU dot whose ``(B, 1, tn, cols)`` view feeds
    the identical epilogue (measurably faster at transformer smoke
    shapes).  The fast path applies unconditionally — under a mesh
    context too — so the sharded and unsharded programs share one
    structure and the same-seed sharded == unsharded contract compares
    identical jaxprs.

    Bit-parity vs the chain oracle: on the einsum path this function is
    *structurally identical* to ``_tiled_read`` and the results agree
    bit for bit (eager vs eager, or jitted vs jitted).  On the fast path
    the flat dot contracts in a different HLO shape, and XLA CPU freely
    contracts mul+add into FMA per lowering — so parity vs the einsum
    oracle there is exact only on FMA-immune operand classes (exact
    per-tile products) and ~1 ulp otherwise; see
    ``tests/test_read_fusion.py`` for the precise contract.
    """
    rows, cols = cfg.rows, cfg.cols
    if transpose:
        rows, cols = cols, rows
        diff = diff.T
    kp, np_ = diff.shape
    b = x_int.shape[0]
    if x_int.shape[1] != kp:
        x_int = jnp.pad(x_int, ((0, 0), (0, kp - x_int.shape[1])))
    tk, tn = kp // rows, np_ // cols
    if tk == 1:
        q = jnp.dot(x_int.astype(jnp.float32), diff.astype(jnp.float32),
                    precision=_HIGHEST)
        q = q.reshape(b, 1, tn, cols)
    else:
        xt = x_int.reshape(b, tk, rows)
        dt = diff.reshape(tk, rows, tn, cols)
        q = jnp.einsum("btr,trnc->btnc", xt.astype(jnp.float32),
                       dt.astype(jnp.float32), precision=_HIGHEST)
    q, sat = integrator_saturation(q, cfg.adc, n_rows=rows,
                                   g_max=cfg.device.gmax,
                                   reduce_axes=(0, 3))
    q = adc_quantize(q, sat, cfg.adc)
    q = replicate_for_exact_reduce(q)
    # A single reduce op, same as the chain path (see the _tiled_read
    # comment: an unrolled add chain would FMA-fuse with the ADC's
    # code*lsb multiply per-compilation and break cross-program bitwise
    # stability).
    return q.sum(axis=1).reshape(b, np_)


def _read_one_jnp(x: Array, g: Array, ref: Array, w_scale: Array,
                  cfg: CrossbarConfig, transpose: bool) -> Array:
    """One matrix: quantise → twin tiled read → rescale (all f32)."""
    x_int, x_scale = quantize_input(x, cfg.adc)
    diff = pad_to_tiles(g - ref, cfg.rows, cfg.cols)
    out_dim = g.shape[0] if transpose else g.shape[1]
    q = _tiled_read_twin(x_int, diff, cfg, transpose)[:, :out_dim]
    return q * (x_scale / w_scale)


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------

def xbar_fused_read_inline(x: Array, g: Array, ref: Array, w_scale,
                           cfg: CrossbarConfig, *, transpose: bool = False,
                           block_b: Optional[int] = None,
                           impl: Optional[str] = None) -> Array:
    """The fused read, inlined into the caller's trace (no jit wrapper).

    ``x``: (..., B, K) float activations ((..., B, N) when ``transpose``);
    ``g``/``ref``: (..., K, N) conductances with matching lead dims — none
    for a plain matrix, (L,) for a scan-stacked container, (L, E) for an
    expert-batched MoE stack; ``w_scale`` broadcasts over the lead dims.
    Returns (..., B, N) ((..., B, K) when ``transpose``) in ``x.dtype``:

        y ≈ x @ (g - ref) / w_scale        (transpose: x @ (g - ref).T)

    with the full DAC / per-tile integrator+ADC / digital-accumulate
    semantics of ``core.xbar_ops.vmm``/``mvm``.  Input quantisation is
    calibrated per lead index (each matrix is its own physical array with
    its own DAC full scale), matching the vmapped per-expert reference.
    ``block_b`` batches the kernel grid over B; dynamic ADC range matches
    the reference only when one block covers the whole batch (default).
    """
    impl = resolve_read_impl(impl)
    if impl == "chain":
        raise ValueError("impl='chain' is the un-fused reference path — "
                         "call core.xbar_ops.vmm/mvm, which own it")
    in_dtype = x.dtype
    lead = g.shape[:-2]
    if ref.shape != g.shape:
        raise ValueError(f"ref {ref.shape} does not match g {g.shape}")
    if x.ndim != len(lead) + 2 or x.shape[:len(lead)] != lead:
        raise ValueError(f"x {x.shape} does not match container lead dims "
                         f"{lead} of g {g.shape}")
    x = x.astype(jnp.float32)
    g = g.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    w_scale = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), lead)
    if impl == "jnp":
        fn = lambda xx, gg, rr, ws: _read_one_jnp(xx, gg, rr, ws, cfg,
                                                  transpose)
        for _ in lead:
            fn = jax.vmap(fn)
        return fn(x, g, ref, w_scale).astype(in_dtype)
    lyr = 1
    for d in lead:
        lyr *= d
    xf = x.reshape(lyr, *x.shape[len(lead):])
    gf = g.reshape(lyr, *g.shape[len(lead):])
    rf = ref.reshape(lyr, *ref.shape[len(lead):])
    # Per-matrix DAC full scale (adc.quantize_input semantics) and the
    # folded trailing rescale, as one (L, 2) kernel operand.
    x_scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=(1, 2)),
                          1e-12) / cfg.adc.in_levels
    sc = jnp.stack([x_scale, x_scale / w_scale.reshape(lyr)], axis=1)
    y = _pallas_read(xf, gf, rf, sc, cfg, transpose, block_b,
                     interpret=(impl == "interpret"))
    y = y.reshape(*lead, *y.shape[1:]) if lead else y[0]
    return y.astype(in_dtype)


# --------------------------------------------------------------------------
# Manual-collective shard-local read (exact mode)
# --------------------------------------------------------------------------

def manual_collective_read(x: Array, g: Array, ref: Array, w_scale,
                           cfg: CrossbarConfig, meta: ShardMeta, *,
                           transpose: bool = False) -> Array:
    """Shard-local tiled read with ordered partial-sum exchange.

    The exact-mode replacement for gather-then-replay: called from inside
    the train step's ``shard_map`` body, where ``g``/``ref``/``w_scale``
    are this shard's *local* tile blocks (``meta`` carries the global
    geometry and mesh axes) and ``x`` is the full replicated activation.
    Each shard runs the fused tile pipeline on only the blocks it owns;
    the only cross-shard traffic is ordered ``all_gather``s of the small
    digital accumulators — never the conductances — so per-step collective
    bytes scale with activations instead of parameters.

    Bit-parity with the single-device :func:`_tiled_read_twin` program
    holds stage by stage:

      * DAC — input quantisation runs on the full replicated ``x`` per
        matrix (the ``max |x|`` full scale is a global-population
        statistic), then the integer drive lines are *sliced* to the local
        reduction range: identical values to the single-device program's
        corresponding rows.
      * tiles — each ``rows x cols`` tile is wholly owned by one shard
        (``_tile_fit`` divisibility), and the per-tile einsum + dynamic
        integrator range (reduced over batch and in-tile columns only) +
        ADC see exactly the single-device operands.  The flat-dot fast
        path is keyed on the *global* reduction-tile count so both
        programs pick the same structure.
      * combine — per-tile ADC outputs are integers scaled by the tile's
        lsb; :func:`core.shardctx.combine_partials_exact` reassembles the
        reduction-tile axis in at-rest order (arithmetic-free), and the
        single ``q.sum`` then reduces the full axis in single-device
        order.  Output columns / expert blocks gather the same way.

    For expert-batched stacks the expert dim of ``x`` is the capacity
    dispatch buffer: slicing it to the local experts *is* the EP dispatch
    (each shard reads only its own experts' tiles), and the trailing
    expert gather is the combine — gather volume drops by the expert
    count vs gathering every expert's conductances.
    """
    in_dtype = x.dtype
    nlead = g.ndim - 2
    lead_loc = g.shape[:-2]
    gview = meta.view(g.ndim)
    lead_names = meta.lead_names(nlead)
    red_names = meta.col if transpose else meta.row
    out_names = meta.row if transpose else meta.col
    if x.ndim != nlead + 2:
        raise ValueError(f"x {x.shape} does not match lead dims of local "
                         f"g {g.shape} (global {gview})")
    x = x.astype(jnp.float32)
    g = g.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    w_scale = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), lead_loc)

    # DAC: quantise the full replicated activations per matrix.  The
    # per-matrix full scale stays in its *global* lead shape — it feeds
    # the trailing rescale, which runs after the output gathers.
    qfn = lambda xx: quantize_input(xx, cfg.adc)
    for _ in range(nlead):
        qfn = jax.vmap(qfn)
    x_int, x_scale = qfn(x)

    # EP dispatch: slice lead (expert) dims to this shard's block, and
    # gather the (tiny) per-expert write scales to global lead shape for
    # the trailing rescale.
    for d in range(nlead):
        if not lead_names[d]:
            continue
        start = shard_index(meta, lead_names[d]) * lead_loc[d]
        x_int = jax.lax.dynamic_slice_in_dim(x_int, start, lead_loc[d],
                                             axis=d)
        w_scale = combine_partials_exact(w_scale, lead_names[d], axis=d)

    # Slice drive lines to the local reduction range.
    red_loc = g.shape[-1] if transpose else g.shape[-2]
    if red_names:
        start = shard_index(meta, red_names) * red_loc
        x_int = jax.lax.dynamic_slice_in_dim(x_int, start, red_loc,
                                             axis=x_int.ndim - 1)

    rows, cols = (cfg.cols, cfg.rows) if transpose else (cfg.rows, cfg.cols)
    # Global reduction-tile count: pins the twin's fast-path choice so the
    # local program mirrors the single-device structure.  (A sharded
    # reduction dim implies multiple global tiles, so the fast path only
    # ever fires with the reduction unsharded — where local == global.)
    red_glob = gview[-1] if transpose else gview[-2]
    gtk = -(-red_glob // rows)

    def _tiles_one(x_i: Array, g2: Array, r2: Array) -> Array:
        diff = pad_to_tiles(g2 - r2, cfg.rows, cfg.cols)
        if transpose:
            diff = diff.T
        kp, np_ = diff.shape
        b = x_i.shape[0]
        if x_i.shape[1] != kp:
            x_i = jnp.pad(x_i, ((0, 0), (0, kp - x_i.shape[1])))
        tk, tn = kp // rows, np_ // cols
        if gtk == 1:
            q = jnp.dot(x_i.astype(jnp.float32), diff.astype(jnp.float32),
                        precision=_HIGHEST)
            q = q.reshape(b, 1, tn, cols)
        else:
            xt = x_i.reshape(b, tk, rows)
            dt = diff.reshape(tk, rows, tn, cols)
            q = jnp.einsum("btr,trnc->btnc", xt.astype(jnp.float32),
                           dt.astype(jnp.float32), precision=_HIGHEST)
        q, sat = integrator_saturation(q, cfg.adc, n_rows=rows,
                                       g_max=cfg.device.gmax,
                                       reduce_axes=(0, 3))
        return adc_quantize(q, sat, cfg.adc)

    fn = _tiles_one
    for _ in range(nlead):
        fn = jax.vmap(fn)
    q = fn(x_int, g, ref)  # (lead_loc..., B, tk_loc, tn_loc, cols)

    # Ordered combine of the per-tile digital accumulators, then a single
    # reduce over the full tile axis in single-device order (an unrolled
    # add chain would FMA-fuse per-compilation; see _tiled_read_twin).
    tile_axis = nlead + 1
    q = combine_partials_exact(q, red_names, axis=tile_axis)
    y = q.sum(axis=tile_axis)
    y = y.reshape(*y.shape[:-2], y.shape[-2] * cols)
    # Crop tile padding on an unsharded out dim (a sharded out dim is
    # tile-divisible, so its local block carries no padding).
    out_loc = g.shape[-2] if transpose else g.shape[-1]
    y = y[..., :out_loc]
    # Combine: gather output columns, then expert blocks, into global order.
    y = combine_partials_exact(y, out_names, axis=y.ndim - 1)
    for d in range(nlead - 1, -1, -1):
        y = combine_partials_exact(y, lead_names[d], axis=d)
    # Trailing digital rescale, AFTER the gathers: elementwise, so it
    # commutes with the (arithmetic-free) combines — and placing it here
    # keeps the multiply adjacent to its downstream consumer exactly as
    # in the single-device program, so XLA's per-fusion FMA contraction
    # of ``y * scale + <consumer add>`` makes the same choice in both
    # lowerings (the bit-parity boundary the module docstring describes).
    y = y * (x_scale / w_scale)[..., None, None]
    return y.astype(in_dtype)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "transpose", "block_b", "impl"))
def _fused_read_jit(x, g, ref, w_scale, cfg, transpose, block_b, impl):
    return xbar_fused_read_inline(x, g, ref, w_scale, cfg,
                                  transpose=transpose, block_b=block_b,
                                  impl=impl)


def xbar_fused_read(x: Array, g: Array, ref: Array, w_scale,
                    cfg: CrossbarConfig, *, transpose: bool = False,
                    block_b: Optional[int] = None,
                    impl: Optional[str] = None) -> Array:
    """Jit'd :func:`xbar_fused_read_inline` for eager callers.

    ``impl`` is resolved *outside* the jit cache so backend / mesh-context
    dispatch never serves a stale cached choice.
    """
    impl = resolve_read_impl(impl)
    return _fused_read_jit(x, g, ref, w_scale, cfg, transpose, block_b,
                           impl)
