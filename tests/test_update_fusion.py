"""The fused analog update path: layer-batched kernel equivalence,
the one-pass contraction of the write drivers' codes and its fallback,
hoisted symbolic-zero tapes, and the in-kernel counter PRNG."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (IDEAL, LINEARIZED, TAOX, AdcConfig,
                        CrossbarConfig, weights_to_conductance)
from repro.core.tiled_analog import (is_analog_container, merge_tapes,
                                     split_tapes, with_tapes)
from repro.core.xbar_ops import (quantize_update_codes,
                                 quantize_update_operands,
                                 update_code_dtype)
from repro.data.synthetic import batch_tokens, make_token_stream
from repro.kernels.xbar_update import (field_normals, write_one_pass,
                                       write_operands, xbar_outer_update)
from repro.models import model as M
from repro.train.analog_lm import init_state, make_analog_sgd_step

TAOX_NN = TAOX.replace(write_noise=0.0)


def _stacked(lyr=3, k=40, n=24, b=6, rows=16, cols=16, device=TAOX_NN,
             seed=0):
    cfg = CrossbarConfig(rows=rows, cols=cols, device=device,
                         adc=AdcConfig())
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(keys[0], (lyr, k, n)) / np.sqrt(k)
    g, ws = jax.vmap(lambda wl: weights_to_conductance(wl, cfg))(w)
    x = jax.random.normal(keys[1], (lyr, b, k))
    d = jax.random.normal(keys[2], (lyr, b, n)) * 0.2
    x_q, d_q = jax.vmap(lambda xl, dl: quantize_update_operands(
        xl, dl, cfg))(x, d)
    scale = -0.05 * ws
    return cfg, g, x_q, d_q, scale


def _cfg(**kw):
    base = dict(dtype="float32", analog=True, analog_mode="device",
                analog_device="taox-nonoise", analog_rows=64,
                analog_cols=64, analog_in_bits=8, analog_out_bits=8)
    base.update(kw)
    return get_config("lm100m", smoke=True).replace(**base)


def _batch(cfg, b=4, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                  jnp.int32)}


# ----------------------------------------------------- layer-batched kernel

@pytest.mark.parametrize("impl", ["fused", "interpret"])
def test_batched_update_matches_per_layer_loop(impl):
    """One (L, K, N) sweep must equal L independent 2-D updates."""
    cfg, g, x_q, d_q, scale = _stacked()
    batched = xbar_outer_update(g, x_q, d_q, scale, cfg, impl=impl)
    looped = jnp.stack([
        xbar_outer_update(g[i], x_q[i], d_q[i], scale[i], cfg, impl=impl)
        for i in range(g.shape[0])])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(looped),
                               rtol=1e-6, atol=1e-7)


def test_batched_update_host_noise_matches_per_layer_loop():
    """Host-field mode: the batched sweep consumes the stacked field the
    same way the per-layer loop consumes its slices."""
    cfg, g, x_q, d_q, scale = _stacked(device=TAOX)
    noise = jax.random.normal(jax.random.PRNGKey(9), g.shape,
                              dtype=jnp.float32)
    batched = xbar_outer_update(g, x_q, d_q, scale, cfg, noise=noise,
                                noise_mode="host", impl="fused")
    looped = jnp.stack([
        xbar_outer_update(g[i], x_q[i], d_q[i], scale[i], cfg,
                          noise=noise[i], noise_mode="host", impl="fused")
        for i in range(g.shape[0])])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(looped),
                               rtol=1e-6, atol=1e-7)


def test_fused_impl_matches_interpret_kernel_with_in_kernel_noise():
    """The jnp twin and the Pallas kernel generate bit-identical noise from
    the same seed, so their updates agree to float tolerance."""
    cfg, g, x_q, d_q, scale = _stacked(device=TAOX)
    seed = jnp.uint32(1234)
    a = xbar_outer_update(g, x_q, d_q, scale, cfg, seed=seed,
                          noise_mode="kernel", impl="fused")
    b = xbar_outer_update(g, x_q, d_q, scale, cfg, seed=seed,
                          noise_mode="kernel", impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ in-kernel PRNG

def test_in_kernel_prng_reproducible_and_seed_sensitive():
    cfg, g, x_q, d_q, scale = _stacked(device=TAOX)
    upd = lambda s: xbar_outer_update(g, x_q, d_q, scale, cfg,
                                      seed=jnp.uint32(s),
                                      noise_mode="kernel", impl="fused")
    np.testing.assert_array_equal(np.asarray(upd(7)), np.asarray(upd(7)))
    assert float(jnp.max(jnp.abs(upd(7) - upd(8)))) > 0.0


def test_in_kernel_prng_distribution_sanity():
    """The counter PRNG's normals: correct moments and tails, no adjacent
    correlation, decorrelated across layers and tiles."""
    cfg = CrossbarConfig(rows=64, cols=64, device=TAOX, adc=AdcConfig())
    z = np.asarray(field_normals(jnp.uint32(42), (2, 256, 256), cfg))
    flat = z.ravel()
    assert abs(flat.mean()) < 0.01
    assert abs(flat.std() - 1.0) < 0.01
    assert abs((np.abs(flat) > 1.96).mean() - 0.05) < 0.005
    assert abs(np.corrcoef(flat[:-1], flat[1:])[0, 1]) < 0.01
    assert abs(np.corrcoef(z[0].ravel(), z[1].ravel())[0, 1]) < 0.01


def test_in_kernel_noise_statistics_match_device_model():
    """With a linearized device, (g_new - g - dg_req) / sigma over all
    cells must be standard normal — same law the host-field path obeys."""
    dev = LINEARIZED  # dg = dg_req + sigma * noise, no state dependence
    cfg, g, x_q, d_q, scale = _stacked(lyr=2, k=128, n=128, b=4,
                                       rows=64, cols=64, device=dev)
    scale = 0.02 * jnp.ones_like(scale)  # small: no rail clipping
    g = 0.5 * jnp.ones_like(g)           # mid-window
    g_new = xbar_outer_update(g, x_q, d_q, scale, cfg,
                              seed=jnp.uint32(3), noise_mode="kernel",
                              impl="fused")
    dg_req = scale[:, None, None] * jnp.einsum("lbk,lbn->lkn", x_q, d_q)
    sigma = dev.write_noise * dev.pulse_dg * jnp.sqrt(
        jnp.abs(dg_req) / dev.pulse_dg)
    zed = np.asarray((g_new - g - dg_req))[np.asarray(sigma) > 1e-9]
    zed = zed / np.asarray(sigma)[np.asarray(sigma) > 1e-9]
    assert abs(zed.mean()) < 0.02
    assert abs(zed.std() - 1.0) < 0.02


# ------------------------------------------------------- pulse-train writes

def _pulse_np(g, x_q, d_q, scale, dev, noise=None):
    """Pure-numpy twin of the pulse-train epilogue: sign-decomposed 4-phase
    outer product -> integer SET/RESET event counts -> per-train device
    response.  Kept deliberately independent of the jax implementation."""
    g = np.asarray(g, np.float32)
    x = np.asarray(x_q, np.float32)
    d = np.asarray(d_q, np.float32)
    m = np.asarray(scale, np.float32)[:, None, None]
    acc = np.einsum("lbk,lbn->lkn", x, d)
    a_abs = np.einsum("lbk,lbn->lkn", np.abs(x), np.abs(d))
    s_mag = 0.5 * (a_abs * np.abs(m) + acc * m)
    r_mag = 0.5 * (a_abs * np.abs(m) - acc * m)
    n_set = np.round(np.maximum(s_mag, 0.0) / dev.pulse_dg)
    n_reset = np.round(np.maximum(r_mag, 0.0) / dev.pulse_dg)
    if dev.kind in ("ideal", "linearized"):
        up = np.ones_like(g)
        dn = np.ones_like(g)
    else:
        xn = (g - dev.gmin) / (dev.gmax - dev.gmin)

        def factor(xx, nu):
            if nu < 1e-6:
                return 2.0 * (1.0 - xx)
            e = np.exp(-nu)
            mid = (np.exp(-0.5 * nu) - e) / (1.0 - e)
            return (np.exp(-nu * xx) - e) / (1.0 - e) / mid

        up = dev.gain_set * factor(xn, dev.nu_set)
        dn = dev.gain_reset * factor(1.0 - xn, dev.nu_reset)
    dg = dev.pulse_dg * (n_set * up - n_reset * dn)
    if dev.write_noise > 0.0 and noise is not None:
        sigma = dev.write_noise * dev.pulse_dg * np.sqrt(n_set + n_reset)
        dg = dg + sigma * np.asarray(noise, np.float32)
    return np.minimum(np.maximum(g + dg, dev.gmin), dev.gmax)


@pytest.mark.parametrize("impl", ["fused", "interpret"])
def test_pulse_train_matches_numpy_twin(impl):
    """Noiseless nonlinear device: both execution paths of the pulse-train
    mode must reproduce the independent numpy reference."""
    cfg, g, x_q, d_q, scale = _stacked()
    out = xbar_outer_update(g, x_q, d_q, scale, cfg, impl=impl,
                            update_mode="pulse_train")
    ref = _pulse_np(g, x_q, d_q, scale, TAOX_NN)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


def test_pulse_train_host_noise_matches_numpy_twin():
    """Host-field noise: sigma scales with the *total* fired event count
    sqrt(n_set + n_reset), which the numpy twin recomputes from scratch."""
    cfg, g, x_q, d_q, scale = _stacked(device=TAOX)
    noise = jax.random.normal(jax.random.PRNGKey(11), g.shape,
                              dtype=jnp.float32)
    out = xbar_outer_update(g, x_q, d_q, scale, cfg, noise=noise,
                            noise_mode="host", impl="fused",
                            update_mode="pulse_train")
    ref = _pulse_np(g, x_q, d_q, scale, TAOX, noise=np.asarray(noise))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


def test_pulse_train_kernel_noise_fused_matches_interpret():
    """The counter PRNG stays bit-identical across backends in pulse-train
    mode too — the noise field depends only on (seed, layer, tile, cell)."""
    cfg, g, x_q, d_q, scale = _stacked(device=TAOX)
    seed = jnp.uint32(77)
    a = xbar_outer_update(g, x_q, d_q, scale, cfg, seed=seed,
                          noise_mode="kernel", impl="fused",
                          update_mode="pulse_train")
    b = xbar_outer_update(g, x_q, d_q, scale, cfg, seed=seed,
                          noise_mode="kernel", impl="interpret",
                          update_mode="pulse_train")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_pulse_train_quantisation_bound_and_outer_equivalence():
    """Ideal noiseless device, mid-window conductances: the pulse-train
    write equals the requested update m*acc up to one pulse_dg of count
    quantisation (each rail rounds to within half an event)."""
    from repro.core.device import IDEAL
    cfg, g, x_q, d_q, scale = _stacked(device=IDEAL)
    g = 0.5 * jnp.ones_like(g)          # mid-window: no rail clipping
    scale = 0.01 * jnp.ones_like(scale)  # small: stay inside the window
    out = xbar_outer_update(g, x_q, d_q, scale, cfg, impl="fused",
                            update_mode="pulse_train")
    req = scale[:, None, None] * jnp.einsum("lbk,lbn->lkn", x_q, d_q)
    err = np.abs(np.asarray(out - g - req))
    assert float(err.max()) <= IDEAL.pulse_dg + 1e-6


def test_pulse_train_differs_from_outer_on_nonlinear_device():
    """On a nonlinear device the per-train response is not the aggregate
    response: the two update modes must not coincide."""
    cfg, g, x_q, d_q, scale = _stacked()
    a = xbar_outer_update(g, x_q, d_q, scale, cfg, impl="fused",
                          update_mode="outer")
    b = xbar_outer_update(g, x_q, d_q, scale, cfg, impl="fused",
                          update_mode="pulse_train")
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4


# ------------------------------------------- one-pass contraction of codes

# An ideal device with a window no update reaches: from g = 0 the written
# conductance is the requested update itself, so the accumulator shows
# through the epilogue bit for bit.  Unit pulses make a pulse train's
# event counts the accumulators themselves.
OPEN = IDEAL.replace(gmin=-1e9, gmax=1e9, pulse_dg=1.0)


def _tapes(reps=(), t=6, in_bits=8, upd_col_bits=4, lyr=3, k=40, n=24,
           seed=0):
    """Tapes as the train step's backward pass writes them: the write
    drivers' codes in the dtype that holds them, and each application's
    (x_scale, d_scale); with the dequantised operands of the same
    activations and errors (``quantize_update_operands``) beside them."""
    cfg = CrossbarConfig(rows=16, cols=16, device=OPEN,
                         adc=AdcConfig(in_bits=in_bits),
                         upd_col_bits=upd_col_bits)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (lyr, *reps, t, k))
    d = jax.random.normal(keys[1], (lyr, *reps, t, n)) * 0.2
    per_matrix = lambda f: functools.reduce(
        lambda h, _: jax.vmap(h), range(1 + len(reps)), f)
    x_int, d_int, xs, ds = per_matrix(
        lambda a, b: quantize_update_codes(a, b, cfg))(x, d)
    x_q, d_q = per_matrix(
        lambda a, b: quantize_update_operands(a, b, cfg))(x, d)
    code = update_code_dtype(cfg)
    tapes = (x_int.astype(code), d_int.astype(code),
             jnp.stack([xs, ds], -1))
    scale = -0.05 * jnp.linspace(0.5, 1.5, lyr, dtype=jnp.float32)
    return cfg, tapes, (x_q, d_q), scale


def _int_sum(a, b):
    return np.einsum("lbk,lbn->lkn", np.asarray(a, np.float32)
                     .astype(np.int64), np.asarray(b, np.float32)
                     .astype(np.int64))


@pytest.mark.parametrize("impl", ["fused", "interpret"])
@pytest.mark.parametrize("mode", ["outer", "pulse_train"])
def test_one_pass_accumulator_is_the_exact_integer_sum(impl, mode):
    """8-bit row and 4-bit column codes go to the kernel in bfloat16, one
    MXU pass, and its accumulators are the exact integer outer products:
    the outer write is the folded scale times the int64 sum, bit for bit;
    a pulse train at scale 2 with unit pulses fires acc + |acc|_abs SET
    and |acc|_abs - acc RESET events, so with a unit noise field it
    writes 2 acc + sqrt(2 a_abs), bit for bit."""
    cfg, (x, d, s), _, scale = _tapes(t=64)
    xb, db, m, one_pass = write_operands(x, d, s, scale, cfg)
    assert one_pass and xb.dtype == db.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(m),
                                  np.asarray(scale * (s[:, 0] * s[:, 1])))
    acc = _int_sum(xb, db)
    g = jnp.zeros(acc.shape, jnp.float32)
    if mode == "outer":
        out = xbar_outer_update(g, xb, db, m, cfg, impl=impl,
                                noise_mode="none", block_b=16)
        want = np.asarray(m)[:, None, None] * acc.astype(np.float32)
    else:
        a_abs = _int_sum(jnp.abs(xb), jnp.abs(db))
        pcfg = cfg.replace(device=OPEN.replace(write_noise=1.0))
        out = xbar_outer_update(g, xb, db, 2.0, pcfg, impl=impl,
                                noise=jnp.ones_like(g), noise_mode="host",
                                update_mode="pulse_train", block_b=16)
        want = ((2 * acc).astype(np.float32)
                + np.sqrt((2 * a_abs).astype(np.float32)))
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("operands", ["signed", "magnitude"])
def test_one_pass_agrees_with_dequantised_highest_accumulator(operands):
    """The old path contracted the dequantised operands in float32
    (HIGHEST), rounding every product and partial sum; the new one scales
    the exact integer sum once.  They agree within float32 rounding of a
    T-term sum: (T + 3) unit roundoffs of |scale| sum |x_q| |d_q|.  The
    magnitudes are the pulse train's second accumulator."""
    t = 64
    cfg, (x, d, s), (x_q, d_q), scale = _tapes(t=t)
    if operands == "magnitude":
        x, d, x_q, d_q = (jnp.abs(a) for a in (x, d, x_q, d_q))
    xb, db, m, _ = write_operands(x, d, s, scale, cfg)
    g = jnp.zeros((x.shape[0], x.shape[-1], d.shape[-1]), jnp.float32)
    new = xbar_outer_update(g, xb, db, m, cfg, impl="fused",
                            noise_mode="none")
    old = xbar_outer_update(g, x_q, d_q, scale, cfg, impl="fused",
                            noise_mode="none")
    bound = (t + 3) * 2.0 ** -24 * np.abs(np.asarray(scale))[:, None, None] \
        * np.einsum("lbk,lbn->lkn", np.abs(np.asarray(x_q, np.float64)),
                    np.abs(np.asarray(d_q, np.float64)))
    diff = np.abs(np.asarray(new, np.float64) - np.asarray(old, np.float64))
    assert (diff <= bound).all()
    assert diff.max() > 0      # the two really are different sums


@pytest.mark.parametrize("case", ["upd_col_bits", "tokens", "reused"])
def test_write_fallback_reproduces_dequantised_bits(case):
    """Where one bfloat16 pass would not be exact — column codes past 2**8
    (10-bit voltage coder), sums past 2**24 (9-bit coders over 260
    tokens), or a reused container whose tapes hold a scale pair per
    application — the write dequantises the codes to exactly the operands
    ``quantize_update_operands`` gives and contracts them in float32, so
    the written conductances are today's, bit for bit."""
    kw = {"upd_col_bits": dict(upd_col_bits=10),
          "tokens": dict(in_bits=9, upd_col_bits=9, t=260),
          "reused": dict(reps=(2,))}[case]
    cfg, (x, d, s), (x_q, d_q), scale = _tapes(**kw)
    cfg = cfg.replace(device=TAOX)
    assert update_code_dtype(cfg) == (jnp.float32 if case == "upd_col_bits"
                                      else jnp.bfloat16)
    assert write_one_pass(cfg, x.shape[-2]) == (case == "reused")
    xf, df, m, one_pass = write_operands(x, d, s, scale, cfg)
    assert not one_pass and xf.dtype == df.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(m), np.asarray(scale))
    np.testing.assert_array_equal(np.asarray(xf), np.asarray(x_q))
    np.testing.assert_array_equal(np.asarray(df), np.asarray(d_q))
    # The reused container's applications add up in its token axis.
    flat = lambda a: a.reshape(a.shape[0], -1, a.shape[-1])
    g = jnp.full((x.shape[0], x.shape[-1], d.shape[-1]), 0.5, jnp.float32)
    for impl in ("fused", "interpret"):
        got = xbar_outer_update(g, flat(xf), flat(df), m, cfg,
                                seed=jnp.uint32(5), noise_mode="kernel",
                                impl=impl, block_b=16)
        want = xbar_outer_update(g, flat(x_q), flat(d_q), scale, cfg,
                                 seed=jnp.uint32(5), noise_mode="kernel",
                                 impl=impl, block_b=16)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch,share", [("lm100m", "one"),
                                        ("zamba2-1.2b", "part")])
def test_write_one_pass_share(arch, share):
    """The step's metric: every written cell of a dense decoder goes
    through the one-pass contraction; a hybrid's shared block, one weight
    set applied at every group boundary, tapes a scale pair per
    application and takes the float32 fallback."""
    cfg = get_config(arch, smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_device="taox-nonoise", analog_rows=64, analog_cols=64)
    step = make_analog_sgd_step(cfg, lr=0.05)
    _, mets = step(init_state(jax.random.PRNGKey(0), cfg), _batch(cfg),
                   jax.random.PRNGKey(1))
    got = float(mets["write_one_pass_share"])
    assert got == np.float32(step.write_one_pass_share)
    if share == "one":
        assert got == 1.0
    else:
        assert 0.0 < got < 1.0


# --------------------------------------------------- hoisted symbolic tapes

def test_split_merge_roundtrip():
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    diff, frozen = split_tapes(params, 8)
    merged = merge_tapes(diff, frozen)
    ref = with_tapes(params, 8)
    assert jax.tree_util.tree_structure(merged) \
        == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hoisted_grads_carry_only_tapes_for_containers():
    """The grads tree of the hoisted loss must hold exactly the tape
    cotangents for analog containers — no g/ref/w_scale leaves at all."""
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    n_tokens = batch["tokens"].size
    diff, frozen = split_tapes(params, n_tokens)
    (_, _), grads = jax.value_and_grad(
        lambda d: M.loss_fn(merge_tapes(d, frozen), batch, cfg),
        has_aux=True)(diff)

    def walk(p, g):
        if is_analog_container(p):
            assert set(g) == {"x_tape", "d_tape", "s_tape"}
        elif isinstance(p, dict):
            for k in p:
                walk(p[k], g[k])
    walk(params, grads)


def test_hoisted_grads_match_with_tapes_reference():
    """Hoisting g/ref/w_scale out of the differentiated tree changes what
    cotangents exist, not their values: tapes and digital grads must be
    identical to the legacy with_tapes gradient."""
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    n_tokens = batch["tokens"].size

    diff, frozen = split_tapes(params, n_tokens)
    (loss_h, _), grads_h = jax.value_and_grad(
        lambda d: M.loss_fn(merge_tapes(d, frozen), batch, cfg),
        has_aux=True)(diff)
    (loss_r, _), grads_r = jax.value_and_grad(
        M.loss_fn, has_aux=True)(with_tapes(params, n_tokens), batch, cfg)

    np.testing.assert_allclose(float(loss_h), float(loss_r), rtol=1e-6)

    def walk(gh, gr):
        if isinstance(gh, dict) and "x_tape" in gh:
            np.testing.assert_allclose(np.asarray(gh["x_tape"]),
                                       np.asarray(gr["x_tape"]),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(gh["d_tape"]),
                                       np.asarray(gr["d_tape"]),
                                       rtol=1e-6, atol=1e-7)
        elif isinstance(gh, dict):
            for k in gh:
                walk(gh[k], gr[k])
        else:
            np.testing.assert_allclose(np.asarray(gh), np.asarray(gr),
                                       rtol=1e-6, atol=1e-7)
    walk(grads_h, grads_r)


# ----------------------------------------------------- refactored train step

def test_step_impl_paths_agree():
    """The fused host path and the Pallas interpreter produce the same
    trained conductances for a noiseless device."""
    cfg = _cfg()
    batch = _batch(cfg)

    def one(impl):
        state = init_state(jax.random.PRNGKey(0), cfg)
        step = make_analog_sgd_step(cfg, lr=0.05, impl=impl)
        new, _ = step(state, batch, jax.random.PRNGKey(5))
        return new["params"]["layers"]["ffn"]["w_upgate"]["g"]

    np.testing.assert_allclose(np.asarray(one("fused")),
                               np.asarray(one("interpret")),
                               rtol=1e-5, atol=1e-6)


def test_step_noise_modes_reproduce_per_seed():
    """kernel-mode noise: same step key reproduces, different keys diverge;
    host mode still works behind the flag."""
    cfg = _cfg(analog_device="taox")
    batch = _batch(cfg)

    def one(key, noise_mode):
        state = init_state(jax.random.PRNGKey(0), cfg)
        step = make_analog_sgd_step(cfg, lr=0.05, noise_mode=noise_mode)
        new, _ = step(state, batch, key)
        return new["params"]["layers"]["ffn"]["w_upgate"]["g"]

    a = one(jax.random.PRNGKey(3), "kernel")
    b = one(jax.random.PRNGKey(3), "kernel")
    c = one(jax.random.PRNGKey(4), "kernel")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(a - c))) > 0.0
    h = one(jax.random.PRNGKey(3), "host")
    assert h.shape == a.shape and bool(jnp.all(jnp.isfinite(h)))


def test_refactored_step_compiles_once_and_learns():
    """No-retrace guard on the hoisted/split step + loss decreases."""
    cfg = _cfg()
    state = init_state(jax.random.PRNGKey(0), cfg)
    step = make_analog_sgd_step(cfg, lr=0.1)
    stream = make_token_stream(50_000, cfg.vocab, seed=0)
    key = jax.random.PRNGKey(1)
    losses = []
    for i in range(15):
        x, y = batch_tokens(stream, 8, 16, i)
        key, ks = jax.random.split(key)
        state, mets = step(state, {"tokens": jnp.asarray(x),
                                   "labels": jnp.asarray(y)}, ks)
        losses.append(float(mets["loss"]))
    assert step.compiles == 1
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < losses[0]
