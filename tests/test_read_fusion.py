"""Bit-parity contract of the fused analog read (kernels/xbar_vmm.py).

The fused kernel replaced the op-by-op chain (quantise → tiled einsum +
ADC → rescale) as the production read path; ``impl="chain"`` keeps the
pre-fusion program alive in ``core.xbar_ops`` as the parity oracle.
These tests enforce the contract stated in the module docstring of
``kernels/xbar_vmm.py``:

  * the fused jnp twin is bit-identical to the chain whenever it takes
    the einsum path (structurally the same program), jit-vs-jit;
  * the interpret-mode Pallas kernel is bit-identical to the chain in
    ``fixed`` range mode with a power-of-two ADC lsb — arbitrary data,
    ragged edge tiles, multi-tile grids, both read directions, on both
    of the kernel's contractions (three bfloat16 passes against an exact
    split of ``G - G_ref`` where the drive codes are exact in bfloat16,
    one float32 ``HIGHEST`` dot where they are not) — the CI bit-check:
    every fused stage runs end to end and no FMA contraction or
    reduction-order choice can move a bit because all partial sums are
    exact;
  * in ``dynamic`` range mode the saturation bound is a data-dependent
    float reduction whose lowering differs between the kernel body and
    the chain's 4-D reduce, so only ~ulp-level agreement is defined.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (IDEAL, TAOX, AdcConfig, CrossbarConfig,
                        make_reference, weights_to_conductance)
from repro.core.adc import (adc_quantize, integrator_range,
                            integrator_saturation)
from repro.core.xbar_ops import mvm as core_mvm
from repro.core.xbar_ops import vmm as core_vmm
from repro.kernels import ops
from repro.kernels.xbar_vmm import (_adc_epilogue, _charge_stats,
                                    resolve_read_impl, split_bf16x3,
                                    xbar_fused_read, xbar_fused_read_inline)

# Power-of-two ADC lsb class: sat = 0.03125 * 127 * 16 * gmax keeps the
# saturation bound and the lsb exact powers of two times gmax, so every
# ADC output is exactly representable and partial sums stay exact.
POW2_ADC = dict(in_bits=8, out_bits=8, range_mode="fixed",
                sat_frac=0.03125)
# The same class with a 10-bit DAC, whose codes (up to 511) bfloat16 does
# not hold: the kernel keeps its float32 HIGHEST dot.  sat = 0.03125 *
# 511 * 16 * gmax over 511 ADC levels keeps the lsb at gmax / 2.
POW2_ADC_10 = dict(POW2_ADC, in_bits=10, out_bits=10)


def _setup(k, n, rows=16, cols=16, adc=None, seed=0):
    cfg = CrossbarConfig(rows=rows, cols=cols, device=IDEAL,
                         adc=AdcConfig(**(adc or {})))
    kw = jax.random.PRNGKey(seed)
    w = jax.random.normal(kw, (k, n)) / np.sqrt(k)
    g, ws = weights_to_conductance(w, cfg)
    ref = make_reference((k, n), cfg)
    return cfg, g, ref, ws


# ------------------------------------------------- twin vs chain (jnp path)

@pytest.mark.parametrize("range_mode", ["dynamic", "fixed"])
@pytest.mark.parametrize("k,n,b", [(40, 24, 6), (64, 48, 8), (33, 40, 3)])
def test_twin_bitwise_chain_vmm(range_mode, k, n, b):
    """Multi-reduction-tile shapes: the twin takes the einsum path and
    must match the chain bit for bit, compiled program vs compiled
    program (this is the program the same-seed sharded==unsharded
    contract rides on)."""
    cfg, g, ref, ws = _setup(k, n, adc={"range_mode": range_mode})
    x = jax.random.normal(jax.random.PRNGKey(1), (b, k))
    y_chain = jax.jit(
        lambda x_: core_vmm(x_, g, ref, ws, cfg, impl="chain"))(x)
    y_twin = jax.jit(
        lambda x_: core_vmm(x_, g, ref, ws, cfg, impl="jnp"))(x)
    np.testing.assert_array_equal(np.asarray(y_chain), np.asarray(y_twin))


@pytest.mark.parametrize("range_mode", ["dynamic", "fixed"])
def test_twin_bitwise_chain_mvm(range_mode):
    cfg, g, ref, ws = _setup(40, 48, adc={"range_mode": range_mode})
    d = jax.random.normal(jax.random.PRNGKey(2), (5, 48))
    y_chain = jax.jit(
        lambda d_: core_mvm(d_, g, ref, ws, cfg, impl="chain"))(d)
    y_twin = jax.jit(
        lambda d_: core_mvm(d_, g, ref, ws, cfg, impl="jnp"))(d)
    np.testing.assert_array_equal(np.asarray(y_chain), np.asarray(y_twin))


def test_twin_flat_dot_fastpath_close_to_chain():
    """Single reduction tile (K <= rows): the twin collapses to one flat
    MXU dot — structurally a different program from the chain's einsum,
    so only allclose (not bitwise) is defined."""
    cfg, g, ref, ws = _setup(16, 40, adc={"range_mode": "dynamic"})
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16))
    y_chain = core_vmm(x, g, ref, ws, cfg, impl="chain")
    y_twin = core_vmm(x, g, ref, ws, cfg, impl="jnp")
    np.testing.assert_allclose(np.asarray(y_twin), np.asarray(y_chain),
                               rtol=1e-6, atol=1e-6)


# -------------------------------------- interpret kernel vs chain (bitwise)

def _pow2_cases(shapes):
    """Each shape with the 8-bit DAC (three bfloat16 passes) under its
    plain id, then with the 10-bit DAC (the HIGHEST dot)."""
    ids = ["-".join(map(str, sh)) for sh in shapes]
    return ([pytest.param(*sh, POW2_ADC, id=i) for sh, i in zip(shapes, ids)]
            + [pytest.param(*sh, POW2_ADC_10, id=f"{i}-in_bits10")
               for sh, i in zip(shapes, ids)])


@pytest.mark.parametrize("k,n,b,adc", _pow2_cases([
    (16, 16, 4),    # exact single tile
    (40, 24, 6),    # ragged padding on both dims
    (64, 48, 8),    # multi-tile both dims
]))
def test_interpret_bitwise_chain_fixed_pow2_vmm(k, n, b, adc):
    """The CI bit-check: in the fixed/power-of-two-lsb class the fused
    kernel (DAC, differential subtract, MXU, ADC epilogue, rescale — all
    in one pallas_call) reproduces the chain exactly."""
    cfg, g, ref, ws = _setup(k, n, adc=adc)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, k))
    y_chain = core_vmm(x, g, ref, ws, cfg, impl="chain")
    y_ker = core_vmm(x, g, ref, ws, cfg, impl="interpret")
    np.testing.assert_array_equal(np.asarray(y_chain), np.asarray(y_ker))


@pytest.mark.parametrize("k,n,b,adc", _pow2_cases([(40, 24, 6), (48, 64, 5)]))
def test_interpret_bitwise_chain_fixed_pow2_mvm(k, n, b, adc):
    cfg, g, ref, ws = _setup(k, n, adc=adc)
    d = jax.random.normal(jax.random.PRNGKey(5), (b, n))
    y_chain = core_mvm(d, g, ref, ws, cfg, impl="chain")
    y_ker = core_mvm(d, g, ref, ws, cfg, impl="interpret")
    np.testing.assert_array_equal(np.asarray(y_chain), np.asarray(y_ker))


def test_interpret_dynamic_range_ulp_close():
    """Dynamic range mode: the kernel computes the per-tile RMS range
    inside the kernel body while the chain reduces over a 4-D layout —
    different lowerings of the same reduction, so agreement is bounded
    by one rounding of the calibration plus FMA contraction, not exact."""
    cfg, g, ref, ws = _setup(40, 24, adc={"range_mode": "dynamic"})
    x = jax.random.normal(jax.random.PRNGKey(6), (6, 40))
    y_chain = core_vmm(x, g, ref, ws, cfg, impl="chain")
    y_ker = core_vmm(x, g, ref, ws, cfg, impl="interpret")
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_chain),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------- the kernel's contraction of G - G_ref

def _reconstructs(a, split=split_bf16x3):
    hi, mid, lo = split(jnp.asarray(a, jnp.float32))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    f32 = lambda p: np.asarray(p, np.float32)
    np.testing.assert_array_equal(f32(hi) + f32(mid) + f32(lo),
                                  np.asarray(a, np.float32))


@pytest.mark.parametrize("device", [IDEAL, TAOX], ids=["ideal", "taox"])
def test_bf16_split_is_exact_on_conductance_tiles(device):
    """hi + mid + lo gives back every bit of a tile's differential
    conductance: programmed weights and states anywhere in the window."""
    cfg = CrossbarConfig(rows=1024, cols=1024, device=device)
    kw, kg = jax.random.split(jax.random.PRNGKey(12))
    w = jax.random.normal(kw, (1024, 1024)) / 32.0
    g_prog, _ = weights_to_conductance(w, cfg)
    g_any = jax.random.uniform(kg, (1024, 1024), minval=device.gmin,
                               maxval=device.gmax)
    ref = make_reference((1024, 1024), cfg)
    for g in (g_prog, g_any):
        # Eagerly, and traced into one compiled program.
        for split in (split_bf16x3, jax.jit(split_bf16x3)):
            _reconstructs(g - ref, split)


def test_bf16_split_is_exact_across_exponents():
    """Both signs, exponents over 2**-100 .. 2**100, full significands."""
    rng = np.random.default_rng(13)
    mant = rng.uniform(1.0, 2.0, (256, 256))
    sign = rng.choice([-1.0, 1.0], (256, 256))
    a = (sign * mant * 2.0 ** rng.integers(-100, 101, (256, 256))
         ).astype(np.float32)
    _reconstructs(a)
    _reconstructs(np.array([0.0, -0.0, 1.0, -1.0, 2.0 ** -100,
                            np.nextafter(np.float32(1), np.float32(2))],
                           np.float32))


def _kernel_dots(adc, transpose):
    """(lhs dtype, rhs dtype, precision) of every dot in the fused read's
    kernel body, traced as a float32 model traces it: with JAX's default
    matmul precision at "highest"."""
    cfg = CrossbarConfig(rows=16, cols=16, device=IDEAL, adc=adc)
    g = jnp.zeros((40, 24))
    x = jnp.zeros((5, 24 if transpose else 40))
    with jax.default_matmul_precision("highest"):
        jaxpr = jax.make_jaxpr(lambda x_, g_: xbar_fused_read_inline(
            x_, g_, g_, 1.0, cfg, transpose=transpose,
            impl="interpret"))(x, g)

    def walk(jx, in_kernel):
        for eqn in jx.eqns:
            inner = in_kernel or eqn.primitive.name == "pallas_call"
            if in_kernel and eqn.primitive.name == "dot_general":
                prec = eqn.params["precision"]
                yield (eqn.invars[0].aval.dtype, eqn.invars[1].aval.dtype,
                       prec[0] if isinstance(prec, tuple) else prec)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, inner)

    return list(walk(jaxpr.jaxpr, False))


@pytest.mark.parametrize("transpose", [False, True], ids=["vmm", "mvm"])
@pytest.mark.parametrize("in_bits", [8, 9, 10])
def test_read_contraction_follows_the_dac_width(in_bits, transpose):
    """Codes of up to 256 levels (DACs of 8 and 9 bits) are exact in
    bfloat16 and take three bfloat16 passes; a 10-bit DAC (511 levels)
    keeps one float32 HIGHEST dot."""
    dots = _kernel_dots(AdcConfig(in_bits=in_bits), transpose)
    if in_bits <= 9:
        assert dots == [(jnp.bfloat16, jnp.bfloat16,
                         jax.lax.Precision.DEFAULT)] * 3
    else:
        assert dots == [(jnp.float32, jnp.float32,
                         jax.lax.Precision.HIGHEST)]


# ----------------------------------------------- epilogue + batched layouts

def test_adc_epilogue_is_the_chain_ops():
    """The in-kernel epilogue must stay literally integrator_saturation +
    adc_quantize — the accuracy model depends on those semantics."""
    cfg = CrossbarConfig(rows=16, cols=16, device=IDEAL,
                         adc=AdcConfig(**POW2_ADC))
    q = 40.0 * jax.random.normal(jax.random.PRNGKey(7), (4, 16))
    want, sat = integrator_saturation(q, cfg.adc, n_rows=cfg.rows,
                                      g_max=cfg.device.gmax)
    want = adc_quantize(want, sat, cfg.adc)
    got = _adc_epilogue(q, _kernel_range(q, cfg), cfg)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def _kernel_range(q, cfg):
    return integrator_range(*_charge_stats(q), cfg.adc, cfg.rows,
                            cfg.device.gmax)


def test_kernel_dynamic_range_is_the_chain_range():
    """Dynamic mode: the kernel accumulates the range statistics strip by
    strip; zero-padded token rows must not move them, and the range must
    agree with integrator_saturation's whole-block reduce to one
    rounding."""
    cfg = CrossbarConfig(rows=16, cols=16, device=IDEAL, adc=AdcConfig())
    q = 40.0 * jax.random.normal(jax.random.PRNGKey(7), (6, 16))
    _, sat = integrator_saturation(q, cfg.adc, n_rows=cfg.rows,
                                   g_max=cfg.device.gmax)
    padded = jnp.pad(q, ((0, 2), (0, 0)))
    np.testing.assert_array_equal(np.asarray(_kernel_range(padded, cfg)),
                                  np.asarray(_kernel_range(q, cfg)))
    np.testing.assert_allclose(float(_kernel_range(q, cfg)),
                               float(sat.reshape(())), rtol=1e-6)


@pytest.mark.parametrize("lead", [(3,), (2, 2)])
def test_batched_interpret_bitwise_per_matrix(lead):
    """The layer-batched (L, K, N) and expert-flattened (L, E, K, N)
    grids must equal running the single-matrix kernel per lead index —
    one pallas_call over the lead axis is purely a launch optimisation."""
    cfg, g0, ref0, ws = _setup(40, 24, adc=POW2_ADC)
    kx = jax.random.PRNGKey(8)
    g = jnp.stack([g0 * (1.0 + 0.1 * i) for i in range(np.prod(lead))]
                  ).reshape(lead + g0.shape)
    ref = jnp.broadcast_to(ref0, lead + ref0.shape)
    x = jax.random.normal(kx, lead + (5, 40))
    y_bat = xbar_fused_read(x, g, ref, ws, cfg, impl="interpret")
    for idx in np.ndindex(*lead):
        y_one = xbar_fused_read(x[idx], g[idx], ref[idx], ws, cfg,
                                impl="interpret")
        np.testing.assert_array_equal(np.asarray(y_bat[idx]),
                                      np.asarray(y_one))


def test_fakequant_kernel_matches_jnp_twin():
    adc = AdcConfig(in_bits=8, out_bits=8)
    x = jax.random.normal(jax.random.PRNGKey(9), (10, 40))
    w = jax.random.normal(jax.random.PRNGKey(10), (40, 24)) / np.sqrt(40)
    y_jnp = ops.fakequant_project(x, w, adc, rows=16, impl="jnp")
    y_ker = ops.fakequant_project(x, w, adc, rows=16, impl="interpret")
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_jnp),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- dispatch contracts

def test_unknown_impl_raises():
    cfg, g, ref, ws = _setup(16, 16)
    x = jnp.ones((2, 16))
    with pytest.raises(ValueError, match="impl"):
        core_vmm(x, g, ref, ws, cfg, impl="mosaic")
    with pytest.raises(ValueError, match="impl"):
        resolve_read_impl("fused")


def test_auto_read_impl_keeps_the_kernel_on_a_one_device_mesh(monkeypatch):
    """On a TPU, "auto" takes the Mosaic kernel unless a mesh of more than
    one device is active (whose exact-reduce pins only the twin carries):
    a one-chip launcher that installs its 1x1 mesh keeps the kernel."""
    from repro.core import shardctx
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = type("Mesh", (), {"size": 1})()
    prev = shardctx.get_shard_context()
    try:
        assert resolve_read_impl("auto") == "pallas"
        shardctx.set_shard_context(mesh, None)
        assert resolve_read_impl("auto") == "pallas"
        mesh.size = 4
        assert resolve_read_impl("auto") == "jnp"
    finally:
        shardctx.set_shard_context(*prev)


def test_fused_read_rejects_mismatched_lead_dims():
    cfg, g, ref, ws = _setup(40, 24)
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 5, 40))  # lead (3,)
    with pytest.raises(ValueError):
        xbar_fused_read(x, jnp.broadcast_to(g, (2,) + g.shape),
                        jnp.broadcast_to(ref, (2,) + ref.shape),
                        ws, cfg, impl="jnp")


def test_analog_serve_decode_never_retraces():
    """The serve decode read rides the fused path (cfg.analog_read_impl
    "auto" -> the fused twin on CPU); per-request scale factors are
    traced values, so serving more requests must not retrace decode."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import SamplingParams, make_engine

    cfg = get_config("lm100m", smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_device="taox-nonoise", analog_rows=64, analog_cols=64)
    params = M.init_params(jax.random.PRNGKey(0), cfg.digital())
    eng = make_engine(cfg, M.program_digital(params, cfg),
                      max_len=32, n_slots=2, prefill_chunk=4)
    sp = SamplingParams(max_new_tokens=4)
    eng.generate([[3, 1, 4, 1]], sp)
    eng.generate([[2, 7], [1, 8, 2]], sp)
    assert eng.decode_compiles == 1
