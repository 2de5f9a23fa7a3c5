"""Pallas TPU kernel: fused, layer-batched rank-k outer-product update.

The paper's parallel write (Fig. 3c) updates every crossbar cell with the
product of its row drive (time-coded activation) and column drive
(voltage-coded error).  On TPU this fuses into: accumulate the batch outer
product for one G tile in VMEM, then push the aggregate request through the
nonlinear/asymmetric/stochastic device model elementwise and write the new
conductances — one HBM round-trip for G instead of three (read, add,
write-back) plus a separate (K, N) gradient materialisation.

Grid layout
-----------
``(L, K/rows, N/cols, B/blk_b)`` with the batch innermost.  ``L`` is a
leading *layer* grid dimension so one ``pallas_call`` sweeps a whole
scan-stacked ``(L, K, N)`` parameter container (every projection of every
transformer layer) instead of launching L kernels from a Python loop and
re-stacking the results.  Containers with richer lead dims ride the same
grid: the registry (``core/analog_registry.flatten_lead``) flattens an
MoE expert stack ``(L, E, K, N)`` expert-outermost onto the layer axis —
the rank-k write of layers x experts is still one launch — and collapses
the per-application tape dim of reused weight sets into the batch axis.
Per-layer scalars (the folded ``-lr * w_scale``, and the PRNG seed with
its tile offsets) ride in whole as SMEM operands indexed by the layer grid
coordinate.  The output block doubles as the outer-product accumulator
until the last batch step, when the device epilogue transforms it into the
new conductances in place, one row strip at a time (the in-kernel normals
and the epilogue's temporaries stay strip-sized, and so does the compiled
body).

Contraction
-----------
The train step hands the kernel the write drivers' integer codes, not
their dequantised values (:func:`write_operands`): row codes up to 127
(the 8-bit temporal coder) and column codes up to 7 (the 4-bit voltage
coder), in bfloat16, which holds them exactly, with the two scalar scales
of each matrix folded into its per-layer scale.  Every product is then an
exact integer in float32, and so is every partial sum below 2**24, so one
bfloat16 MXU pass with float32 accumulation (two for pulse trains, whose
|x| |d| accumulator is a second contraction) forms the exact integer
outer product, which the epilogue scales once.  Float32 operands (the
dequantised values public callers pass, and the train step's fallback
where the codes or their sums would not be exact) take the full-precision
float32 dot, six passes on the MXU.  The kernel tells the two apart by
the operands' dtype.

Stochasticity
-------------
Three modes (``noise_mode``):

* ``"none"``   — noiseless devices; no noise operand at all.
* ``"kernel"`` — the default for training: standard normals are generated
  *inside* the epilogue by a counter-based PRNG (murmur-mix of
  (seed, layer, tile, cell pair) + Box–Muller, the two legs of a pair on
  rows ``r`` and ``r + rows/2``) seeded per (layer, tile) from one
  scalar.  No (K, N) noise field ever exists in HBM, and because the
  generator is plain uint32/f32 arithmetic with no carried state it
  produces the same samples in the compiled TPU kernel, in interpret
  mode, and in the fused jnp path below — one seed, the same noise on
  every backend (bit for bit wherever the transcendentals are the same
  code).
* ``"host"``   — the legacy pre-generated N(0,1) field rides in as an
  input; kept as the fallback that reproduces ``core.device.apply_update``
  exactly for a given ``jax.random`` key (the kernel-vs-reference
  equivalence tests depend on it).

Switch matrix (every ``impl`` x ``noise_mode`` pair is valid):

    impl \\ noise_mode   "none"        "kernel"            "host"
    "pallas"            Mosaic        Mosaic + ctr PRNG   Mosaic + field
    "interpret"         oracle        oracle + ctr PRNG   oracle + field
    "fused"             jnp twin      jnp + field_normals jnp + field

"interpret" and "fused" generate bit-identical noise from the same seed
and agree on the conductances to f32 rounding
(tests/test_update_fusion.py).  So does the compiled kernel on a TPU v5e:
its batch sum associates by token block, and its transcendentals need
not match XLA's, so a pulse-train event count can round the other way
(max |kernel - twin| 9.2e-6 for "outer", one pulse in 1.3e-5 of the
cells for "pulse_train", on the full-width lm100m wqkv stack —
chip_smoke.py).

Sharding
--------
:func:`xbar_sharded_update` runs the same layer-batched update under
``shard_map`` on a device mesh: each shard owns whole ``rows x cols``
tiles of the container (specs from
``launch/sharding.analog_update_specs``), the token contraction of the
outer product stays shard-local (tapes ride in pre-sliced), and the
counter PRNG is made *shard-invariant* by offsetting the (layer, tile)
counters with the shard's global base tile coordinates
(``tile_offsets``).  One seed therefore produces bit-identical
conductances on a 1-device and an N-device mesh — the acceptance contract
of the sharded analog train step (tests/test_sharded_analog.py).

Execution paths (``impl``)
--------------------------
``"pallas"`` compiles the kernel with Mosaic (TPU), ``"interpret"`` runs it
under the Pallas interpreter (the validation oracle on any backend), and
``"fused"`` runs a mathematically identical single-sweep jnp twin — one
batched einsum + the same epilogue — which is what non-TPU hosts use for
speed: the interpreter walks the grid serially and exists for correctness,
not throughput.  ``"auto"`` picks ``"pallas"`` on TPU and ``"fused"``
elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.core.crossbar import CrossbarConfig
from repro.core.device import DeviceConfig
from repro.core.xbar_ops import update_code_dtype, update_code_levels
from repro.kernels.xbar_vmm import VMEM_LIMIT_CAP

Array = jax.Array

NOISE_MODES = ("none", "host", "kernel")
IMPLS = ("auto", "pallas", "interpret", "fused")
# "outer": one aggregate analog write per cell from the batched outer
# product (the default).  "pulse_train": sign-decomposed 4-phase stochastic
# pulse trains (Gokmen & Vlasov, arXiv:1603.07341) — SET and RESET event
# magnitudes are accumulated separately and quantised to integer
# clock-cycle counts before the asymmetric device responds to each train.
UPDATE_MODES = ("outer", "pulse_train")
# Full f32 contractions on every backend (see kernels/xbar_vmm.py).
_HIGHEST = jax.lax.Precision.HIGHEST


def _contract_precision(dtype):
    """Precision of the outer-product contraction of operands of
    ``dtype``: one MXU pass for bfloat16 codes, whose products float32
    holds exactly, full float32 otherwise.  DEFAULT, stated: a float32
    model sets JAX's default matmul precision to HIGHEST, which Mosaic
    refuses on bfloat16 operands."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else _HIGHEST


# --------------------------------------------------------------------------
# Counter-based PRNG (shared by the kernel epilogue and the fused path)
# --------------------------------------------------------------------------

def _u32(x) -> Array:
    return jnp.asarray(x).astype(jnp.uint32)


def _mix32(x: Array) -> Array:
    """murmur3 fmix32: a bijective 32-bit finaliser with full avalanche."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _tile_seed(seed, layer, tile_k, tile_n) -> Array:
    """Decorrelated per-(layer, tile) seed from one scalar base seed."""
    h = _mix32(_u32(seed) ^ jnp.uint32(0x9E3779B9))
    h = _mix32(h + jnp.uint32(0x9E3779B1) * _u32(layer))
    h = _mix32(h + jnp.uint32(0x85EBCA77) * _u32(tile_k))
    h = _mix32(h + jnp.uint32(0xC2B2AE3D) * _u32(tile_n))
    return h


def _pair_normals(h: Array) -> tuple:
    """Two standard normals per hashed pair counter: both Box–Muller
    outputs, so the hash/log work is paid once per *pair*.  The one mixed
    word supplies both uniforms (16 bits each — radius resolution 1.5e-5
    truncates at 4.7 sigma, far beyond the device-noise regime).  Pure
    uint32/f32 ops — no carried RNG state — the same hash gives the same
    samples everywhere.  Each 16-bit half goes to f32 through int32 (exact
    below 2**16; Mosaic has no uint32 -> f32 cast)."""
    hi = (h >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (h & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    # u1 in (0, 1] keeps the log finite.
    u1 = (hi + 1.0) * (1.0 / (1 << 16))
    u2 = lo * (1.0 / (1 << 16))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    a = (2.0 * np.pi) * u2
    return r * jnp.cos(a), r * jnp.sin(a)


def _pair_rows(rows: int) -> tuple:
    """(legs, pair_rows) of a tile's normal field.  A tile with even
    ``rows`` (every practical array) pairs row ``r`` with ``r + rows/2``:
    one Box–Muller draw fills both cells, so it does half the hashing and
    half the logs.  An odd tile spends a full draw per cell and keeps
    only the cosine leg."""
    return (2, rows // 2) if rows % 2 == 0 else (1, rows)


def _strip_normals(seed: Array, r0, n: int, cols: int, legs: int) -> tuple:
    """Normals of pair rows ``[r0, r0 + n)`` of one tile: ``legs`` arrays
    of shape (..., n, cols), leg ``j`` belonging to tile rows
    ``r0 + j * pair_rows``.  The counter of pair (r, c) is ``r * cols +
    c``; ``seed`` broadcasts against the (n, cols) strip."""
    pid = ((jax.lax.broadcasted_iota(jnp.int32, (n, cols), 0) + r0)
           * cols
           + jax.lax.broadcasted_iota(jnp.int32, (n, cols), 1))
    z = _pair_normals(_mix32(pid.astype(jnp.uint32) ^ seed))
    return z[:legs]


def field_normals(seed, shape, cfg: CrossbarConfig,
                  tile_offsets=(0, 0, 0)) -> Array:
    """(L, K, N) standard-normal field, bit-identical to what the kernel
    epilogue generates per (layer, tile).  Used by the fused jnp path and by
    the distribution/reproducibility tests; never needed on TPU.

    ``tile_offsets`` = (layer, row-tile, col-tile) base coordinates of this
    block in a larger (sharded) container: a shard holding tiles
    ``[k0:k0+tk, n0:n0+tn]`` of layer ``l0`` passes ``(l0, k0, n0)`` and
    gets exactly the corresponding slice of the global field, making the
    noise invariant to how the container is sharded."""
    lyr, k, n = shape
    rows, cols = cfg.rows, cfg.cols
    tk, tn = -(-k // rows), -(-n // cols)
    l0, k0, n0 = (_u32(o) for o in tile_offsets)
    li = jax.lax.broadcasted_iota(jnp.uint32, (lyr, tk, tn), 0) + l0
    ki = jax.lax.broadcasted_iota(jnp.uint32, (lyr, tk, tn), 1) + k0
    ni = jax.lax.broadcasted_iota(jnp.uint32, (lyr, tk, tn), 2) + n0
    seeds = _tile_seed(seed, li, ki, ni)[..., None, None]
    legs, prow = _pair_rows(rows)
    z = jnp.concatenate(_strip_normals(seeds, 0, prow, cols, legs),
                        axis=-2)  # (L, tk, tn, rows, cols)
    z = z.transpose(0, 1, 3, 2, 4).reshape(lyr, tk * rows, tn * cols)
    return z[:, :k, :n]


# --------------------------------------------------------------------------
# Device epilogue (elementwise; mirrors core.device.apply_update)
# --------------------------------------------------------------------------

def _updown_factors(g: Array, dev: DeviceConfig) -> tuple:
    """State-dependent SET/RESET step factors (see core.device.set_factor)."""
    x = (g - dev.gmin) / (dev.gmax - dev.gmin)

    # set/reset factors, centre-normalised (see core.device.set_factor)
    def factor(xx, nu):
        if nu < 1e-6:
            return 2.0 * (1.0 - xx)
        e = np.exp(-nu)
        mid = (np.exp(-0.5 * nu) - e) / (1.0 - e)
        return (jnp.exp(-nu * xx) - e) / (1.0 - e) / mid

    if dev.nu_set == dev.nu_reset and dev.nu_set >= 1e-6:
        # Symmetric nonlinearity: exp(-nu (1-x)) = e^{-nu} / exp(-nu x),
        # so one transcendental serves both write directions.
        nu = dev.nu_set
        e = np.exp(-nu)
        mid = (np.exp(-0.5 * nu) - e) / (1.0 - e)
        s = jnp.exp(-nu * x)
        up = dev.gain_set * ((s - e) / ((1.0 - e) * mid))
        dn = dev.gain_reset * ((e / s - e) / ((1.0 - e) * mid))
    else:
        up = dev.gain_set * factor(x, dev.nu_set)
        dn = dev.gain_reset * factor(1.0 - x, dev.nu_reset)
    return up, dn


def _device_epilogue(g: Array, dg_req: Array, noise: Optional[Array],
                     dev: DeviceConfig) -> Array:
    """Elementwise device model (mirrors core.device.apply_update)."""
    if dev.kind in ("ideal", "linearized"):
        dg = dg_req
    else:
        up, dn = _updown_factors(g, dev)
        dg = jnp.where(dg_req >= 0, dg_req * up, dg_req * dn)
    if dev.write_noise > 0.0 and noise is not None:
        n_pulses = jnp.abs(dg_req) / dev.pulse_dg
        sigma = dev.write_noise * dev.pulse_dg * jnp.sqrt(n_pulses)
        dg = dg + sigma * noise
    # raw min/max: jnp.clip is a pjit-wrapped call per invocation
    return jnp.minimum(jnp.maximum(g + dg, dev.gmin), dev.gmax)


def _pulse_epilogue(g: Array, acc: Array, a_abs: Array, m, noise:
                    Optional[Array], dev: DeviceConfig) -> Array:
    """Pulse-train write (mirrors core.device.apply_pulse_train).

    ``acc = sum_b x_b d_b`` is the signed outer-product accumulator and
    ``a_abs = sum_b |x_b| |d_b|`` its magnitude twin.  The four drive
    phases of the sign-decomposed update (++/-- on the SET rail, +-/-+ on
    the RESET rail) partition the event mass so that

        S = (a_abs |m| + acc m) / 2      R = (a_abs |m| - acc m) / 2

    with ``S - R = m acc`` (the requested update) and ``S + R = |m| a_abs``
    (the total fired charge).  Each rail fires an *integer* number of
    clock-cycle events ``n = round(mag / pulse_dg)``; the device answers
    every SET event with ``pulse_dg * up`` and every RESET event with
    ``pulse_dg * dn``, so nonlinearity and gain asymmetry act per train,
    not per aggregate.  Write noise scales with the total event count
    ``sqrt(n_set + n_reset)`` — a correlated batch (acc ~ a_abs) is as
    quiet as the aggregate write, a cancelling batch keeps the full
    fired-charge variance the "outer" mode never sees.
    """
    s_mag = 0.5 * (a_abs * jnp.abs(m) + acc * m)
    r_mag = 0.5 * (a_abs * jnp.abs(m) - acc * m)
    n_set = jnp.round(jnp.maximum(s_mag, 0.0) / dev.pulse_dg)
    n_reset = jnp.round(jnp.maximum(r_mag, 0.0) / dev.pulse_dg)
    if dev.kind in ("ideal", "linearized"):
        up = jnp.ones_like(g)
        dn = jnp.ones_like(g)
    else:
        up, dn = _updown_factors(g, dev)
    dg = dev.pulse_dg * (n_set * up - n_reset * dn)
    if dev.write_noise > 0.0 and noise is not None:
        sigma = dev.write_noise * dev.pulse_dg * jnp.sqrt(n_set + n_reset)
        dg = dg + sigma * noise
    return jnp.minimum(jnp.maximum(g + dg, dev.gmin), dev.gmax)


# --------------------------------------------------------------------------
# The kernel
# --------------------------------------------------------------------------

def _strip_rows(prow: int, cols: int) -> int:
    """Rows per epilogue strip: the largest divisor of ``prow`` that keeps
    a strip near 32K cells, a multiple of 8 (the sublane tile) where one
    exists."""
    cap = max(8, (1 << 15) // cols)
    divs = [d for d in range(1, min(prow, cap) + 1) if prow % d == 0]
    aligned = [d for d in divs if d % 8 == 0]
    return (aligned or divs)[-1]


def _update_kernel(*refs, cfg: CrossbarConfig, n_bsteps: int,
                   noise_mode: str, update_mode: str = "outer",
                   counted: bool = False):
    """One (layer, row tile, column tile, token block) grid step.

    ``counted``: a scalar-prefetch (L,) operand, first, gives each
    layer's filled tape rows (an expert's routed rows; the rest are
    zero).  Token blocks past them add nothing to the outer product, so
    they are skipped, and their tape blocks are not fetched (the index
    map repeats the last filled block)."""
    if counted:
        rows_ref, *refs = refs
    if update_mode == "pulse_train":
        # Second output block: the |x| |d| magnitude accumulator rides the
        # same tile grid as the outer-product accumulator.
        *refs, a_ref = refs
    else:
        a_ref = None
    if noise_mode == "host":
        x_ref, d_ref, g_ref, noise_ref, scale_ref, o_ref = refs
    elif noise_mode == "kernel":
        x_ref, d_ref, g_ref, seed_ref, scale_ref, o_ref = refs
    else:
        x_ref, d_ref, g_ref, scale_ref, o_ref = refs
    bstep = pl.program_id(3)
    # Program ids and SMEM scalars are read at the kernel-body top level:
    # inside a pl.when branch they would land in a cond jaxpr the
    # interpreter can't lower.
    lid, kid, nid = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    m = scale_ref[lid]
    if counted:
        bb = x_ref.shape[1]
        filled = bstep < (rows_ref[lid] + (bb - 1)) // bb
    if noise_mode == "kernel":
        # seed_ref is [base seed, layer/row/col tile offsets].  Offsets are
        # the shard's global base tile coordinates (zero when unsharded),
        # so the per-tile PRNG stream is indexed by *global* grid position
        # and one seed gives the same noise on any mesh.
        seed = _tile_seed(seed_ref[0],
                          _u32(lid) + seed_ref[1],
                          _u32(kid) + seed_ref[2],
                          _u32(nid) + seed_ref[3])

    @pl.when(bstep == 0)
    def _init():
        o_ref[0, :, :] = jnp.zeros_like(o_ref[0, :, :])
        if a_ref is not None:
            a_ref[0, :, :] = jnp.zeros_like(a_ref[0, :, :])

    precision = _contract_precision(x_ref.dtype)

    def accumulate():
        # The outer product sum_b x[b, :] d[b, :] for this tile.
        o_ref[0, :, :] += jax.lax.dot_general(
            x_ref[0, :, :], d_ref[0, :, :],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        if a_ref is not None:
            a_ref[0, :, :] += jax.lax.dot_general(
                jnp.abs(x_ref[0, :, :]), jnp.abs(d_ref[0, :, :]),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)

    if counted:
        pl.when(filled)(accumulate)
    else:
        accumulate()

    @pl.when(bstep == n_bsteps - 1)
    def _apply():
        # The device epilogue runs over row strips, so the in-kernel
        # normals and the epilogue's temporaries stay strip-sized.  Strip
        # i covers pair rows [i*s, (i+1)*s); its Box–Muller legs land on
        # tile rows r and r + rows/2 (_pair_rows).
        rows, cols = o_ref.shape[-2:]
        legs, prow = _pair_rows(rows)
        s = _strip_rows(prow, cols)

        def strip(i, carry):
            r0 = i * s
            if noise_mode == "kernel":
                zs = _strip_normals(seed, r0, s, cols, legs)
            for j in range(legs):
                rs = pl.ds(pl.multiple_of(r0 + j * prow, s), s)
                if noise_mode == "kernel":
                    noise = zs[j]
                elif noise_mode == "host":
                    noise = noise_ref[0, rs, :]
                else:
                    noise = None
                if a_ref is not None:
                    o_ref[0, rs, :] = _pulse_epilogue(
                        g_ref[0, rs, :], o_ref[0, rs, :], a_ref[0, rs, :],
                        m, noise, cfg.device)
                else:
                    o_ref[0, rs, :] = _device_epilogue(
                        g_ref[0, rs, :], m * o_ref[0, rs, :], noise,
                        cfg.device)
            return carry

        jax.lax.fori_loop(0, prow // s, strip, 0)


def _update_vmem_bytes(b: int, cfg: CrossbarConfig, noise_mode: str,
                      update_mode: str, tape_bytes: int) -> int:
    """Scoped VMEM the update kernel needs for a ``b``-token block: the
    double-buffered tape blocks (``tape_bytes`` a value) and tile-sized
    operand/output blocks, the outer-product temporaries, and 4 MiB of
    slack for the epilogue strips and Mosaic's own scratch."""
    tile = cfg.rows * cfg.cols
    n_tiles = 2 + (noise_mode == "host") + (update_mode == "pulse_train")
    temps = (1 + (update_mode == "pulse_train")) * tile
    return (2 * (tape_bytes * b * (cfg.rows + cfg.cols) + 4 * n_tiles * tile)
            + 4 * temps + (4 << 20))


# Token block of the update's batch grid axis.  The outer-product sum
# accumulates in the resident output block across batch steps, so the
# block size moves only the f32 association; this one keeps the tape
# blocks at 1 MiB and the compiled body small (Mosaic unrolls the
# block's matmul).
UPDATE_BLOCK_B = 256


def _pallas_update(g, x_q, d_q, scale, noise, seed, offs, cfg, block_b,
                   noise_mode, interpret, update_mode="outer", rows=None):
    lyr, k, n = g.shape
    b = x_q.shape[1]
    bb = block_b or min(b, UPDATE_BLOCK_B)
    x_q = jnp.pad(x_q, ((0, 0), (0, (-b) % bb), (0, (-k) % cfg.rows)))
    d_q = jnp.pad(d_q, ((0, 0), (0, (-b) % bb), (0, (-n) % cfg.cols)))
    gp = jnp.pad(g, ((0, 0), (0, (-k) % cfg.rows), (0, (-n) % cfg.cols)))
    _, kp, np_ = gp.shape
    bp = x_q.shape[1]
    grid = (lyr, kp // cfg.rows, np_ // cfg.cols, bp // bb)
    counted = rows is not None

    if counted:
        # Index maps also take the scalar-prefetch row counts; a token
        # block past an expert's rows maps to its last filled block, which
        # is resident already, so nothing is fetched for it.
        def last(r_, l_, b_):
            return jnp.minimum(b_, jnp.maximum((r_[l_] + (bb - 1)) // bb - 1,
                                               0))

        tile_map = lambda l_, k_, n_, b_, r_: (l_, k_, n_)
        x_map = lambda l_, k_, n_, b_, r_: (l_, last(r_, l_, b_), k_)
        d_map = lambda l_, k_, n_, b_, r_: (l_, last(r_, l_, b_), n_)
    else:
        tile_map = lambda l_, k_, n_, b_: (l_, k_, n_)
        x_map = lambda l_, k_, n_, b_: (l_, b_, k_)
        d_map = lambda l_, k_, n_, b_: (l_, b_, n_)
    tile_spec = pl.BlockSpec((1, cfg.rows, cfg.cols), tile_map)
    inputs = [x_q, d_q, gp]
    in_specs = [
        pl.BlockSpec((1, bb, cfg.rows), x_map),
        pl.BlockSpec((1, bb, cfg.cols), d_map),
        tile_spec,
    ]
    if noise_mode == "host":
        inputs.append(jnp.pad(noise, ((0, 0), (0, (-k) % cfg.rows),
                                      (0, (-n) % cfg.cols))))
        in_specs.append(tile_spec)
    elif noise_mode == "kernel":
        inputs.append(jnp.stack([_u32(seed)] + [_u32(o) for o in offs]))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    # The per-layer scales sit whole in SMEM, indexed by the layer grid
    # coordinate inside the body.
    inputs.append(scale)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    g_shape = jax.ShapeDtypeStruct((lyr, kp, np_), jnp.float32)
    if update_mode == "pulse_train":
        # The magnitude accumulator is a second output on the identical
        # tile grid; the caller discards it (scratch that outlives bsteps).
        out_specs = (tile_spec, tile_spec)
        out_shape = (g_shape, g_shape)
    else:
        out_specs = tile_spec
        out_shape = g_shape
    kernel = functools.partial(_update_kernel, cfg=cfg, n_bsteps=grid[3],
                               noise_mode=noise_mode,
                               update_mode=update_mode, counted=counted)
    if counted:
        call = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs))
        inputs = [rows.astype(jnp.int32)] + inputs
    else:
        call = dict(grid=grid, in_specs=in_specs, out_specs=out_specs)
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            VMEM_LIMIT_CAP,
            _update_vmem_bytes(bb, cfg, noise_mode, update_mode,
                               x_q.dtype.itemsize))),
        interpret=interpret,
        name="xbar_update",
        **call,
    )(*inputs)
    if update_mode == "pulse_train":
        out = out[0]
    return out[:, :k, :n]


def _fused_update(g, x_q, d_q, scale, noise, seed, offs, cfg, noise_mode,
                  update_mode="outer"):
    """Single-sweep jnp twin of the kernel: one layer-batched einsum plus
    the identical epilogue (and, in kernel noise mode, the identical
    counter-PRNG bits).  The fast path on hosts without Mosaic.  Its
    contraction of bfloat16 codes is exact too, so kernel and twin agree
    on the accumulator bit for bit."""
    precision = _contract_precision(x_q.dtype)
    acc = jnp.einsum("lbk,lbn->lkn", x_q, d_q,
                     preferred_element_type=jnp.float32, precision=precision)
    if noise_mode == "kernel":
        noise = field_normals(seed, g.shape, cfg, tile_offsets=offs)
    elif noise_mode == "none":
        noise = None
    if update_mode == "pulse_train":
        a_abs = jnp.einsum("lbk,lbn->lkn", jnp.abs(x_q), jnp.abs(d_q),
                           preferred_element_type=jnp.float32,
                           precision=precision)
        return _pulse_epilogue(g, acc, a_abs, scale[:, None, None], noise,
                               cfg.device)
    return _device_epilogue(g, scale[:, None, None] * acc, noise,
                            cfg.device)


def _dispatch_update(g, x_q, d_q, scale, noise, seed, offs, cfg, block_b,
                     impl, noise_mode, update_mode="outer", rows=None):
    if impl == "fused":
        # The zero rows past a layer's count add nothing to the einsum.
        return _fused_update(g, x_q, d_q, scale, noise, seed, offs, cfg,
                             noise_mode, update_mode)
    return _pallas_update(g, x_q, d_q, scale, noise, seed, offs, cfg,
                          block_b, noise_mode,
                          interpret=(impl == "interpret"),
                          update_mode=update_mode, rows=rows)


_outer_update = functools.partial(jax.jit, static_argnames=(
    "cfg", "block_b", "impl", "noise_mode", "update_mode"))(_dispatch_update)


def _drive_dtype(x_q: Array, d_q: Array) -> tuple:
    """The drive operands as the kernel takes them: a bfloat16 pair (the
    write drivers' codes) as it is, anything else in float32."""
    if x_q.dtype == d_q.dtype == jnp.bfloat16:
        return x_q, d_q
    return x_q.astype(jnp.float32), d_q.astype(jnp.float32)


def write_one_pass(cfg: CrossbarConfig, tokens: int) -> bool:
    """Whether one bfloat16 MXU pass contracts ``tokens`` rows of the
    write drivers' codes exactly: bfloat16 holds every code (both coders'
    levels up to 2**8), and the largest sum of products,
    ``levels_x * levels_d * tokens``, stays below 2**24, where float32
    still holds every integer."""
    lx, ld = update_code_levels(cfg)
    return (update_code_dtype(cfg) == jnp.bfloat16
            and lx * ld * tokens < 2 ** 24)


def write_operands(x_tape: Array, d_tape: Array, s_tape: Array, scale,
                   cfg: CrossbarConfig) -> tuple:
    """The rank-k write's drive operands from one container's tapes.

    ``x_tape`` (lead..., [reps,] T, K) / ``d_tape`` (..., T, N): the
    write drivers' integer codes; ``s_tape`` (lead..., [reps,] 2): each
    application's (x_scale, d_scale); ``scale`` (lead...): each matrix's
    folded ``-lr * w_scale``.  Returns ``(x, d, scale, one_pass)``.

    Where one bfloat16 pass contracts the codes exactly
    (:func:`write_one_pass`) and each matrix has one scale pair (a reused
    container tapes one per application), the codes go to the kernel in
    bfloat16 and both scales fold into ``scale``: the kernel forms the
    exact integer outer product and scales it once.  Otherwise they are
    dequantised to the values ``xbar_ops.quantize_update_operands`` gives,
    bit for bit, for the float32 contraction."""
    xs, ds = s_tape[..., 0], s_tape[..., 1]
    one_pass = (s_tape.ndim - 1 == jnp.ndim(scale)
                and write_one_pass(cfg, x_tape.shape[-2]))
    if one_pass:
        return (x_tape.astype(jnp.bfloat16), d_tape.astype(jnp.bfloat16),
                scale * (xs * ds), True)
    return (x_tape.astype(jnp.float32) * xs[..., None, None],
            d_tape.astype(jnp.float32) * ds[..., None, None], scale, False)


def _resolve_update_args(g, x_q, d_q, scale, cfg, noise, seed, noise_mode,
                         impl, interpret, tile_offsets=None,
                         update_mode=None):
    squeeze = g.ndim == 2
    if squeeze:
        g, x_q, d_q = g[None], x_q[None], d_q[None]
        if noise is not None:
            noise = noise[None]
    lyr = g.shape[0]
    dev = cfg.device
    if tile_offsets is None:
        tile_offsets = (0, 0, 0)
    offs = tuple(_u32(o) for o in tile_offsets)

    if noise_mode is None:
        if dev.write_noise <= 0.0:
            noise_mode = "none"
        elif noise is not None:
            noise_mode = "host"
        elif seed is not None:
            noise_mode = "kernel"
        else:
            raise ValueError(
                "stochastic device model requires a noise field "
                "(noise_mode='host') or a scalar seed (noise_mode='kernel')")
    if noise_mode not in NOISE_MODES:
        raise ValueError(f"noise_mode must be one of {NOISE_MODES}")
    if noise_mode == "host" and noise is None:
        raise ValueError("noise_mode='host' requires a noise field")
    if noise_mode == "kernel" and seed is None:
        raise ValueError("noise_mode='kernel' requires a scalar seed")
    if noise_mode != "host":
        noise = None
    if noise_mode != "kernel":
        seed = None

    if impl is None:
        if interpret is not None:
            impl = "interpret" if interpret else "pallas"
        else:
            impl = "auto"
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "fused"
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}")

    if update_mode is None:
        update_mode = getattr(cfg, "update_mode", "outer") or "outer"
    if update_mode not in UPDATE_MODES:
        raise ValueError(f"update_mode must be one of {UPDATE_MODES}")

    g = g.astype(jnp.float32)
    x_q, d_q = _drive_dtype(x_q, d_q)
    if noise is not None:
        noise = noise.astype(jnp.float32)
    if seed is not None:
        seed = _u32(seed)
    scale = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(-1), (lyr,))
    return (g, x_q, d_q, scale, noise, seed, offs, noise_mode, impl,
            update_mode, squeeze)


def xbar_outer_update(g: Array, x_q: Array, d_q: Array, scale,
                      cfg: CrossbarConfig,
                      noise: Optional[Array] = None,
                      block_b: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      seed: Optional[Array] = None,
                      noise_mode: Optional[str] = None,
                      impl: Optional[str] = None,
                      tile_offsets=None,
                      update_mode: Optional[str] = None) -> Array:
    """G <- device(G, scale * sum_b outer(x_q_b, d_q_b)), layer-batched.

    ``g``: (K, N) or scan-stacked (L, K, N) conductances; ``x_q``: (B, K)
    or (L, B, K) row drives; ``d_q``: (B, N) or (L, B, N) column drives
    (already quantised by the write drivers); ``scale`` folds
    ``-lr * w_scale`` — scalar or (L,).  A bfloat16 pair of drives is
    contracted in one MXU pass, anything else in float32 (see the module
    docstring, and :func:`write_operands` for the codes).

    Stochasticity: pass ``seed`` (scalar uint32) for in-kernel noise
    (``noise_mode="kernel"``), or a pre-generated N(0,1) ``noise`` field of
    g's shape (``noise_mode="host"``, the exact twin of
    ``core.device.apply_update`` for the matching ``jax.random`` key).

    ``impl``: "pallas" | "interpret" | "fused" | None ("auto": Mosaic on
    TPU, the fused jnp twin elsewhere).  ``interpret=True/False`` is the
    legacy spelling of "interpret"/"pallas".

    ``tile_offsets``: (layer, row-tile, col-tile) global base coordinates
    of this block when it is a shard of a larger container — shifts the
    in-kernel counter-PRNG streams so shard-local updates reproduce the
    whole-array noise (see :func:`field_normals`).  Default (0, 0, 0).

    ``update_mode``: "outer" (one aggregate write per cell, default) or
    "pulse_train" (sign-decomposed 4-phase pulse trains with integer
    event counts — see :func:`_pulse_epilogue`).  ``None`` defers to
    ``cfg.update_mode``.
    """
    in_dtype = g.dtype
    (g, x_q, d_q, scale, noise, seed, offs, noise_mode, impl,
     update_mode, squeeze) = _resolve_update_args(
         g, x_q, d_q, scale, cfg, noise, seed, noise_mode, impl, interpret,
         tile_offsets, update_mode)
    out = _outer_update(g, x_q, d_q, scale, noise, seed, offs, cfg,
                        block_b, impl, noise_mode, update_mode)
    if squeeze:
        out = out[0]
    return out.astype(in_dtype)


def xbar_outer_update_inline(g: Array, x_q: Array, d_q: Array, scale,
                             cfg: CrossbarConfig,
                             noise: Optional[Array] = None,
                             block_b: Optional[int] = None,
                             seed: Optional[Array] = None,
                             noise_mode: Optional[str] = None,
                             impl: Optional[str] = None,
                             tile_offsets=None,
                             update_mode: Optional[str] = None,
                             rows: Optional[Array] = None) -> Array:
    """``xbar_outer_update`` without the jit wrapper, for callers already
    inside a jitted computation (the analog train step): the update inlines
    into the caller's graph, so per-container epilogues fuse with the rest
    of the step instead of becoming separate pjit subcomputations.

    ``rows`` (L,): each layer's filled tape rows, the first of its B (an
    expert's routed rows); the kernel skips the zero rows past them."""
    in_dtype = g.dtype
    (g, x_q, d_q, scale, noise, seed, offs, noise_mode, impl,
     update_mode, squeeze) = _resolve_update_args(
         g, x_q, d_q, scale, cfg, noise, seed, noise_mode, impl, None,
         tile_offsets, update_mode)
    if rows is not None:
        rows = jnp.reshape(rows, (-1,))
    out = _dispatch_update(g, x_q, d_q, scale, noise, seed, offs, cfg,
                           block_b, impl, noise_mode, update_mode, rows)
    if squeeze:
        out = out[0]
    return out.astype(in_dtype)


# --------------------------------------------------------------------------
# Sharded update (shard_map over the container tile grid)
# --------------------------------------------------------------------------

def _wrap_shard_map(body, mesh, in_specs, out_specs):
    """shard_map with replication checking off: the bodies use
    axis_index/psum patterns the static checker rejects or
    over-restricts."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _flat_axis_index(mesh, names) -> Array:
    """Global shard index over one or more mesh axes, row-major (matches
    how a dim sharded over ("pod", "data") is laid out)."""
    if isinstance(names, str):
        names = (names,)
    idx = jnp.uint32(0)
    for a in names:
        idx = idx * jnp.uint32(mesh.shape[a]) + _u32(jax.lax.axis_index(a))
    return idx


def xbar_sharded_update(g: Array, x_q: Array, d_q: Array, scale,
                        cfg: CrossbarConfig, mesh, specs,
                        noise: Optional[Array] = None,
                        block_b: Optional[int] = None,
                        seed: Optional[Array] = None,
                        noise_mode: Optional[str] = None,
                        impl: Optional[str] = None,
                        update_mode: Optional[str] = None) -> Array:
    """The layer-batched update, run under ``shard_map`` on ``mesh``.

    ``specs`` maps {"g", "x_tape", "d_tape", "scale"} to tile-aligned
    PartitionSpecs (``launch/sharding.analog_update_specs``).  Each shard
    receives whole (rows x cols) tiles of its container block plus the
    matching slices of the tape operands, so the rank-k write is entirely
    local: the token contraction runs over the full (replicated) batch and
    no cross-device reduction exists on this path.  The per-(layer, tile)
    counter-PRNG seeds are offset by the shard's global base tile
    coordinates (``tile_offsets``), which makes one scalar seed produce
    bit-identical conductances on any mesh — including the degenerate
    1-device mesh and the plain unsharded call.

    Works with every ``impl`` path: Mosaic compiles one kernel per shard
    on TPU; the fused jnp twin serves host-platform meshes in CI.
    """
    squeeze = g.ndim == 2
    if squeeze:  # normalise to the stacked layout so specs index uniformly
        g, x_q, d_q = g[None], x_q[None], d_q[None]
        if noise is not None:
            noise = noise[None]
        scale = jnp.asarray(scale, jnp.float32).reshape(1)
        g_spec = P(None, *specs["g"])
        x_spec = P(None, *specs["x_tape"])
        d_spec = P(None, *specs["d_tape"])
        s_spec = P(None)
    else:
        g_spec, x_spec, d_spec = specs["g"], specs["x_tape"], specs["d_tape"]
        s_spec = specs["scale"]
        scale = jnp.broadcast_to(
            jnp.asarray(scale, jnp.float32).reshape(-1), (g.shape[0],))
    rows, cols = cfg.rows, cfg.cols
    row_axes, col_axes = g_spec[-2], g_spec[-1]
    lead_axes = g_spec[0] if len(g_spec) > 2 else None

    def _off(names, n_local_tiles):
        if names is None:
            return jnp.uint32(0)
        return _flat_axis_index(mesh, names) * jnp.uint32(n_local_tiles)

    use_seed = seed is not None
    use_noise = noise is not None

    def body(g_l, x_l, d_l, s_l, *rest):
        rest = list(rest)
        noise_l = rest.pop(0) if use_noise else None
        seed_l = rest.pop(0) if use_seed else None
        offs = (_off(lead_axes, g_l.shape[0]),
                _off(row_axes, g_l.shape[1] // rows),
                _off(col_axes, g_l.shape[2] // cols))
        return xbar_outer_update_inline(
            g_l, x_l, d_l, s_l, cfg, noise=noise_l, block_b=block_b,
            seed=seed_l, noise_mode=noise_mode, impl=impl,
            tile_offsets=offs, update_mode=update_mode)

    operands = [g.astype(jnp.float32), *_drive_dtype(x_q, d_q), scale]
    in_specs = [g_spec, x_spec, d_spec, s_spec]
    if use_noise:
        operands.append(noise.astype(jnp.float32))
        in_specs.append(g_spec)
    if use_seed:
        operands.append(_u32(seed))
        in_specs.append(P())
    out = _wrap_shard_map(body, mesh, tuple(in_specs), g_spec)(*operands)
    return (out[0] if squeeze else out).astype(g.dtype)
