"""The paper's three computational kernels, as pure-JAX simulation ops.

  1. ``vmm``          — parallel read        y = x @ W      (paper Fig. 3a)
  2. ``mvm``          — transpose read       y = d @ W.T    (paper Fig. 3b)
  3. ``outer_update`` — rank-k parallel write W += sum outer (paper Fig. 3c)

Semantics per op (matching the circuit):
  * inputs are DAC-quantised to ``in_bits`` (temporal coding),
  * every 1024x1024 *tile* integrates its own column charge, saturates at the
    integrator dynamic range and is ADC-quantised to ``out_bits``,
  * tile partial sums are accumulated digitally,
  * the update quantises rows to ``in_bits`` (temporal) and columns to
    ``upd_col_bits`` (voltage coding, 4 bits in the paper's 8-bit variant)
    and pushes the outer product through the nonlinear/stochastic device.

These jnp implementations are the reference semantics; the Pallas kernels in
``repro.kernels`` implement the identical math with explicit VMEM tiling and
are validated against ``repro.kernels.ref`` (which re-exports these).

``vmm``/``mvm`` are also the production dispatch point: by default they
execute through the *fused* read (``kernels.xbar_vmm``) — the jnp twin on
CPU, the single DAC→MXU→ADC Pallas kernel on TPU — selected by
``cfg.read_impl`` or the explicit ``impl=`` argument.  ``impl="chain"``
pins the original unfused quantise → pad → tiled-einsum → rescale chain
below, which stays the bit-reference oracle the fused paths are validated
against (tests/test_read_fusion.py spells out the parity contract).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .adc import AdcConfig, adc_quantize, integrator_saturation, quantize_input
from .crossbar import CrossbarConfig, pad_to_tiles
from .device import DeviceConfig, apply_update
from .shardctx import replicate_for_exact_reduce

Array = jax.Array


def _read_conductance(g: Array, cfg: CrossbarConfig,
                      key: Optional[Array]) -> Array:
    """Apply multiplicative read noise (paper §V.A) if configured."""
    if cfg.device.read_noise > 0.0:
        if key is None:
            raise ValueError("read_noise > 0 requires a PRNG key")
        eps = jax.random.normal(key, g.shape, dtype=g.dtype)
        g = g * (1.0 + cfg.device.read_noise * eps)
    return g


def _tiled_read(x_int: Array, diff: Array, cfg: CrossbarConfig,
                transpose: bool) -> Array:
    """Shared body of VMM / MVM: per-tile integrate + saturate + ADC.

    ``x_int``: (B, K) integer drive levels; ``diff``: (K, N) signed
    conductance (G - G_ref), padded to tile multiples.  ``transpose`` reads
    the array column-driven (the MVM of Fig. 3b): reduction runs over N.
    """
    rows, cols = cfg.rows, cfg.cols
    if transpose:
        # Drive columns, integrate rows: reduction dim is the *column* count
        # of the physical array; tile sizes swap roles.
        rows, cols = cols, rows
        diff = diff.T  # logical view; same storage in the kernel version
    kp, np_ = diff.shape
    b = x_int.shape[0]
    tk, tn = kp // rows, np_ // cols
    if x_int.shape[1] != kp:  # pad drive lines to the tile grid
        x_int = jnp.pad(x_int, ((0, 0), (0, kp - x_int.shape[1])))
    xt = x_int.reshape(b, tk, rows)
    dt = diff.reshape(tk, rows, tn, cols)
    # Per-tile analog column charge:  (B, tk, tn, cols)
    q = jnp.einsum("btr,trnc->btnc", xt.astype(jnp.float32),
                   dt.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    # One integrator range per physical tile, shared over batch and columns.
    q, sat = integrator_saturation(q, cfg.adc, n_rows=rows,
                                   g_max=cfg.device.gmax,
                                   reduce_axes=(0, 3))
    q = adc_quantize(q, sat, cfg.adc)
    # Digital accumulation across reduction tiles.  Under a sharded mesh
    # the reduction-tile axis may be sharded (row-tiles of the container);
    # summing it as partial-sum + all-reduce would associate differently
    # per mesh shape, so the sharded analog step's bit-exact contract pins
    # the accumulation order: gather the per-tile ADC outputs (exact, no
    # arithmetic) and reduce locally over the full axis in single-device
    # order.  The ADC boundary is the determinism boundary — everything
    # before it is tile-local.  No-op when no mesh context is installed.
    q = replicate_for_exact_reduce(q)
    # The reduction stays a single jnp.sum (reduce op), NOT an unrolled
    # chain of adds: XLA CPU contracts a bare ``adc_output + acc`` add
    # into an FMA with the preceding ``code * lsb`` multiply on a
    # per-compilation basis, which would make bitwise results depend on
    # the surrounding program (breaking the sharded==single-device
    # contract).  A reduce op never FMA-fuses.  The fused Pallas kernel's
    # grid-sequential accumulator associates differently, but on the
    # operand classes where kernel-vs-chain bit parity is enforced
    # (power-of-two ADC lsb / single reduction tile — see
    # kernels/xbar_vmm.py "Bit-parity contract") every partial sum is
    # exact, so the association order cannot matter there.
    return q.sum(axis=1).reshape(b, np_)


def _resolve_read_impl(cfg: CrossbarConfig, impl: Optional[str]) -> str:
    # Lazy import: repro.kernels imports repro.core at module scope.
    from repro.kernels.xbar_vmm import resolve_read_impl
    if impl is None:
        impl = getattr(cfg, "read_impl", None)
    return resolve_read_impl(impl)


def _chain_read(x: Array, g: Array, g_ref: Array, w_scale: Array,
                cfg: CrossbarConfig, transpose: bool) -> Array:
    """The original unfused read chain — the bit-reference oracle.

    quantise → pad → per-tile einsum + integrator/ADC (``_tiled_read``) →
    crop → rescale.  Lead dims (scan-stacked / expert-batched containers)
    are vmapped one matrix at a time, matching the fused paths' per-matrix
    DAC calibration.
    """
    if g.ndim > 2:
        ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32),
                              g.shape[:-2])
        fn = lambda xx, gg, rr, ws_: _chain_read(xx, gg, rr, ws_, cfg,
                                                 transpose)
        for _ in range(g.ndim - 2):
            fn = jax.vmap(fn)
        return fn(x, g, g_ref, ws)
    in_dtype = x.dtype
    x = x.astype(jnp.float32)
    x_int, x_scale = quantize_input(x, cfg.adc)
    diff = pad_to_tiles(g - g_ref, cfg.rows, cfg.cols)
    out_dim = g.shape[0] if transpose else g.shape[1]
    q = _tiled_read(x_int, diff, cfg, transpose)[:, :out_dim]
    # Pin the read output replicated (no-op without a mesh context): the
    # conductances are the only sharded operands of the analog step, so
    # pinning every array read/write boundary keeps the whole digital
    # interior (attention, norms, loss) replicated — GSPMD propagation in
    # a larger graph is otherwise free to carry the tile sharding into
    # downstream contractions, where a cross-shard reduction would break
    # the bit-exact contract.
    return replicate_for_exact_reduce(
        (q * (x_scale / w_scale)).astype(in_dtype))


def _read(x: Array, g: Array, g_ref: Array, w_scale: Array,
          cfg: CrossbarConfig, key: Optional[Array], impl: Optional[str],
          transpose: bool, meta=None, rows=None) -> Array:
    g = _read_conductance(g, cfg, key)
    if meta is not None and meta.sharded:
        # Exact-mode manual-collective path: ``g``/``g_ref`` are this
        # shard's local tile blocks (we are inside the step's shard_map
        # body); the shard-local read exchanges only the small digital
        # accumulators in pinned order — see kernels/xbar_vmm.py.
        from repro.kernels.xbar_vmm import manual_collective_read
        return manual_collective_read(x, g, g_ref, w_scale, cfg, meta,
                                      transpose=transpose)
    impl = _resolve_read_impl(cfg, impl)
    if impl == "chain":
        return _chain_read(x, g, g_ref, w_scale, cfg, transpose)
    from repro.kernels.xbar_vmm import xbar_fused_read_inline
    return replicate_for_exact_reduce(
        xbar_fused_read_inline(x, g, g_ref, w_scale, cfg,
                               transpose=transpose, impl=impl, rows=rows))


def vmm(x: Array, g: Array, g_ref: Array, w_scale: Array,
        cfg: CrossbarConfig, key: Optional[Array] = None,
        impl: Optional[str] = None, meta=None, rows=None) -> Array:
    """Analog vector-matrix multiply: y ≈ x @ W for W=(g-g_ref)/w_scale.

    ``x``: (..., B, K) float activations; ``g``/``g_ref``: (..., K, N)
    conductances (lead dims for scan-stacked / expert-batched containers).
    ``impl`` overrides ``cfg.read_impl`` (see the module docstring).
    ``meta`` (a ``shardctx.ShardMeta``) routes to the shard-local
    manual-collective read when the container is tile-sharded.
    ``rows`` (lead dims): the rows of each matrix's B that are driven,
    the first ones, the rest being zero; the kernel skips the rest (the
    other paths read them, to the same result).
    """
    return _read(x, g, g_ref, w_scale, cfg, key, impl, transpose=False,
                 meta=meta, rows=rows)


def mvm(d: Array, g: Array, g_ref: Array, w_scale: Array,
        cfg: CrossbarConfig, key: Optional[Array] = None,
        impl: Optional[str] = None, meta=None, rows=None) -> Array:
    """Analog transpose read: y ≈ d @ W.T  (same array, columns driven)."""
    return _read(d, g, g_ref, w_scale, cfg, key, impl, transpose=True,
                 meta=meta, rows=rows)


def _col_coder(cfg: CrossbarConfig) -> AdcConfig:
    """The column write driver's voltage coder (``upd_col_bits``)."""
    return AdcConfig(in_bits=cfg.upd_col_bits, out_bits=cfg.adc.out_bits)


def update_code_levels(cfg: CrossbarConfig) -> Tuple[int, int]:
    """Magnitude levels of the row (temporal) and column (voltage) write
    coders: their codes are integers in [-levels, levels]."""
    return cfg.adc.in_levels, _col_coder(cfg).in_levels


def update_code_dtype(cfg: CrossbarConfig):
    """dtype that carries the write drivers' codes: bfloat16 where its
    8-bit significand holds every code exactly (levels up to 2**8),
    float32 otherwise."""
    return jnp.bfloat16 if max(update_code_levels(cfg)) <= 256 \
        else jnp.float32


def quantize_update_codes(
        x: Array, d: Array, cfg: CrossbarConfig
) -> Tuple[Array, Array, Array, Array]:
    """Quantise the outer-product operands as the write drivers do.

    Rows (x) use the temporal coder (``in_bits``); columns (d) use the
    voltage coder (``upd_col_bits``: 3 magnitude bits + sign in the paper).
    Returns the integer codes and their scalar scales
    (x_int, d_int, x_scale, d_scale), with x ~ x_int * x_scale.
    """
    x_int, x_scale = quantize_input(x, cfg.adc)
    d_int, d_scale = quantize_input(d, _col_coder(cfg))
    return x_int, d_int, x_scale, d_scale


def quantize_update_operands(
        x: Array, d: Array, cfg: CrossbarConfig
) -> Tuple[Array, Array]:
    """The write drivers' operands dequantised: (x_int * x_scale,
    d_int * d_scale) of :func:`quantize_update_codes`."""
    x_int, d_int, x_scale, d_scale = quantize_update_codes(x, d, cfg)
    return x_int * x_scale, d_int * d_scale


def outer_update(g: Array, x: Array, d: Array, lr: float | Array,
                 w_scale: Array, cfg: CrossbarConfig,
                 key: Optional[Array] = None,
                 device: Optional[DeviceConfig] = None) -> Array:
    """Rank-k outer-product update: G <- device(G, -lr * x^T d * w_scale).

    ``x``: (B, K) forward activations, ``d``: (B, N) backprop errors.
    The requested weight change  ΔW = -lr * sum_b outer(x_b, d_b)  is scaled
    into conductance units and pushed through the device model (nonlinearity,
    asymmetry, stochasticity, window clipping).
    """
    device = device or cfg.device
    x_q, d_q = quantize_update_operands(x.astype(jnp.float32),
                                        d.astype(jnp.float32), cfg)
    dw = -(lr) * jnp.einsum("bk,bn->kn", x_q, d_q)
    dg_req = dw * w_scale
    return apply_update(g, dg_req, device, key)
