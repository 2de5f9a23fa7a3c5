"""Mixed-signal periphery: input temporal coding (DAC) and ramp ADC.

Paper §III.A: digital inputs are encoded into variable-length pulse trains
(one pulse per magnitude bit, sign selects drive polarity).  The analog sum
of charge on each column is the exact integer dot product

    q_j = sum_i x_int_i * G_ij        (x_int in [-(2^{b-1}-1), 2^{b-1}-1])

because the per-bit pulse charges add as powers of two.  The integrator has
a finite dynamic range — the paper deliberately sizes the capacitor for only
a few percent of the worst-case charge ("most of the inputs either are zero
or average to near zero, and large values saturate", §IV.D) — and the ramp
ADC digitises to ``out_bits`` levels.

All quantisers here are symmetric mid-tread uniform quantisers so that zero
is exactly representable (critical for sparse activations).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def _clip(x: Array, lo, hi) -> Array:
    """Primitive-level clip.  ``jnp.clip``/``jnp.round`` are jit-wrapped
    calls and the analog sim chain hits them dozens of times per step —
    raw min/max (and ``lax.round`` below) keep the traced graph flat,
    which measurably cuts the train step's trace+compile time."""
    return jnp.minimum(jnp.maximum(x, lo), hi)


@dataclasses.dataclass(frozen=True)
class AdcConfig:
    """Static configuration of the crossbar I/O path.

    ``in_bits``/``out_bits``: 8/8, 4/4 or 2/2 in the paper's three variants
    (one input bit is the sign bit).
    ``sat_frac``: integrator saturation as a fraction of the worst-case
    column charge ``(2^{in_bits-1}-1) * n_rows * g_max``.  The paper's 10 fF
    vs 330 fF sizing corresponds to ~3 %.
    """

    in_bits: int = 8
    out_bits: int = 8
    sat_frac: float = 0.03
    # Integrator/ADC range selection:
    #  * "dynamic": range = sat_sigmas * rms(column charge) per tile — models
    #    a programmable-gain integrator calibrated to the layer's stationary
    #    activation statistics (the paper sizes the capacitor for "a few
    #    percent" of worst case for exactly this reason).
    #  * "fixed": range = sat_frac * worst-case charge (paper's raw sizing).
    range_mode: str = "dynamic"
    sat_sigmas: float = 4.0
    stochastic_round: bool = False

    @property
    def in_levels(self) -> int:
        return 2 ** (self.in_bits - 1) - 1  # magnitude levels (sign separate)

    @property
    def out_levels(self) -> int:
        return 2 ** (self.out_bits - 1) - 1


def _round(x: Array, key: Optional[Array]) -> Array:
    if key is None:
        # round-half-to-even, same as jnp.round minus the pjit wrapper
        return lax.round(x, lax.RoundingMethod.TO_NEAREST_EVEN)
    # Stochastic rounding: floor + Bernoulli(frac).
    f = jnp.floor(x)
    p = x - f
    return f + (jax.random.uniform(key, x.shape, dtype=x.dtype) < p)


def quantize_input(x: Array, cfg: AdcConfig, scale: Optional[Array] = None,
                   key: Optional[Array] = None) -> Tuple[Array, Array]:
    """Quantise activations to signed integers for temporal coding.

    Returns ``(x_int, scale)`` with ``x ≈ x_int * scale`` and
    ``x_int ∈ [-L, L]``, ``L = 2^{in_bits-1}-1``.  ``scale`` defaults to a
    dynamic per-call full-scale (max |x|), matching a digital core that
    normalises before driving the DACs.
    """
    levels = cfg.in_levels
    if scale is None:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / levels
    x_int = _round(x / scale, key if cfg.stochastic_round else None)
    return _clip(x_int, float(-levels), float(levels)), scale


def integrator_saturation(q: Array, cfg: AdcConfig, n_rows: int,
                          g_max: float = 1.0,
                          reduce_axes: Optional[Tuple[int, ...]] = None
                          ) -> Tuple[Array, Array]:
    """Clip accumulated column charge to the integrator dynamic range.

    ``reduce_axes``: axes of ``q`` over which one integrator range is shared
    (e.g. batch and columns of a tile) in ``dynamic`` mode.

    Returns ``(q_clipped, sat_level)`` — ``sat_level`` broadcasts against
    ``q`` and is consumed by :func:`adc_quantize` as the ADC full scale.
    """
    if cfg.range_mode == "fixed":
        sat = jnp.asarray(integrator_range(None, None, cfg, n_rows, g_max),
                          dtype=q.dtype)
    else:  # dynamic: k-sigma of the observed charge
        if reduce_axes is None:
            reduce_axes = tuple(range(q.ndim))
        sumsq = jnp.sum(jnp.square(q), axis=reduce_axes, keepdims=True)
        nz = jnp.sum((q != 0).astype(q.dtype), axis=reduce_axes,
                     keepdims=True)
        sat = integrator_range(sumsq, nz, cfg, n_rows,
                               g_max).astype(q.dtype)
    return _clip(q, -sat, sat), sat


def integrator_range(sumsq, nz, cfg: AdcConfig, n_rows: int,
                     g_max: float = 1.0):
    """The integrator's saturation level, shared by one population of
    column charges, from its statistics: ``sumsq`` (sum of squared
    charge) and ``nz`` (count of nonzero charges).  ``fixed`` mode ignores
    both and returns the capacitor-sized constant.  A kernel that
    accumulates the statistics block by block applies the same rule.

    Tiles at the matrix edge contain zero-padded columns (and a kernel's
    batch block zero-padded rows); normalising by the *non-zero*
    population keeps the range tied to real signal."""
    if cfg.range_mode == "fixed":
        return cfg.sat_frac * (cfg.in_levels * n_rows * g_max)
    rms = jnp.sqrt(sumsq / jnp.maximum(nz, 1.0))
    return jnp.maximum(cfg.sat_sigmas * rms, 1e-6)


def adc_quantize(q: Array, sat: Array, cfg: AdcConfig,
                 key: Optional[Array] = None) -> Array:
    """Ramp ADC: uniform quantisation of [-sat, +sat] to out_bits levels.

    Output is returned in the *same charge units* (dequantised), i.e. the
    digital core sees ``lsb * round(q / lsb)``.
    """
    lsb = sat / cfg.out_levels
    code = _round(q / lsb, key if cfg.stochastic_round else None)
    code = _clip(code, float(-cfg.out_levels), float(cfg.out_levels))
    return code * lsb


def quantize_dequantize(x: Array, cfg: AdcConfig) -> Array:
    """Round-trip input quantisation (testing/fake-quant helper)."""
    x_int, scale = quantize_input(x, cfg)
    return x_int * scale
