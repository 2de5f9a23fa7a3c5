"""Analog transformer training: digital parity, taped-VJP semantics,
Pallas-kernel update routing, and the no-retrace guard."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import apply_update
from repro.core.tiled_analog import (analog_project, crossbar_from_model,
                                     program_linear, readout, tile_info,
                                     with_tapes)
from repro.core.xbar_ops import mvm, quantize_update_operands, vmm
from repro.data.synthetic import batch_tokens, make_token_stream
from repro.models import model as M
from repro.train.analog_lm import init_state, make_analog_sgd_step


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


# --------------------------------------------------------------- containers

def test_program_readout_roundtrip():
    """Programming a digital weight matrix and serially reading it back is
    exact when no value hits the window clip (8x-rms headroom)."""
    cfg = crossbar_from_model(_cfg())
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (100, 70))
    p = program_linear(w, cfg)
    np.testing.assert_allclose(readout(p, cfg), w, rtol=1e-5, atol=1e-7)
    tk, tn, fill = tile_info(p, cfg)
    assert (tk, tn) == (2, 2) and 0.4 < fill < 0.6


def test_taped_matmul_semantics():
    """Forward = VMM, dx = MVM through the same conductances, and the tape
    cotangents are exactly the write drivers' integer codes and their
    scales, whose products are the quantised operands."""
    cfg = crossbar_from_model(_cfg())
    key = jax.random.PRNGKey(1)
    w = 0.1 * jax.random.normal(key, (48, 80))
    p = program_linear(w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 48))
    pt = with_tapes(p, 6)

    y = analog_project(pt, x, cfg)
    np.testing.assert_allclose(
        y, vmm(x, p["g"], p["ref"], p["w_scale"], cfg), rtol=1e-6)

    dy = jax.random.normal(jax.random.PRNGKey(3), y.shape)
    grads, dx = jax.grad(
        lambda pp, xx: jnp.vdot(analog_project(pp, xx, cfg), dy),
        argnums=(0, 1))(pt, x)
    np.testing.assert_allclose(
        dx, mvm(dy, p["g"], p["ref"], p["w_scale"], cfg),
        rtol=1e-5, atol=1e-6)
    x_q, d_q = quantize_update_operands(x, dy, cfg)
    x_scale, d_scale = grads["s_tape"]
    for codes in (grads["x_tape"], grads["d_tape"]):
        np.testing.assert_array_equal(codes, np.round(codes))
    np.testing.assert_allclose(grads["x_tape"] * x_scale, x_q, rtol=1e-6)
    np.testing.assert_allclose(grads["d_tape"] * d_scale, d_q, rtol=1e-6)
    # the dense (K, N) gradient is never formed
    assert float(jnp.max(jnp.abs(grads["g"]))) == 0.0


# ------------------------------------------------------------------ parity

def test_forward_parity_ideal_device_high_bits():
    """Acceptance: with an ideal device, 16-bit I/O and a wide integrator
    range, the analog transformer forward matches the digital forward
    within rtol 1e-2."""
    cfg = _cfg(analog_device="ideal", analog_in_bits=16,
               analog_out_bits=16, analog_sat_sigmas=8.0)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    digital = M.readout_digital(params, cfg)
    batch = _batch(cfg)
    la, *_ = M.forward(params, batch, cfg)
    ld, *_ = M.forward(digital, batch, cfg.digital())
    np.testing.assert_allclose(la, ld, rtol=1e-2, atol=1e-2)


# ----------------------------------------------------------------- updates

def test_update_routes_through_kernel_device_model():
    """One analog-SGD step must move every projection's conductances by the
    Fig. 3c rank-k write: outer(x_q, d_q) scaled into conductance units and
    pushed through the nonlinear device model."""
    cfg = _cfg()
    lr = 0.05
    state = init_state(jax.random.PRNGKey(0), cfg)
    params = state["params"]
    batch = _batch(cfg)

    # reference: tapes from a plain grad of the same injected tree
    _, grads = jax.value_and_grad(M.loss_fn, has_aux=True)(
        with_tapes(params, batch["tokens"].size), batch, cfg)

    step = make_analog_sgd_step(cfg, lr=lr)
    # the step donates its state; keep a live copy for the reference math
    params = jax.tree.map(jnp.copy, params)
    new_state, _ = step(state, batch, jax.random.PRNGKey(9))

    dev = crossbar_from_model(cfg).device
    for name in ("attn", "ffn"):
        sub = params["layers"][name]
        gsub = grads["layers"][name]
        nsub = new_state["params"]["layers"][name]
        leaf = "wqkv" if name == "attn" else "w_upgate"
        for layer in range(sub[leaf]["g"].shape[0]):
            p, g, n = sub[leaf], gsub[leaf], nsub[leaf]
            x_scale, d_scale = g["s_tape"][layer]
            dw = jnp.einsum("bk,bn->kn", g["x_tape"][layer] * x_scale,
                            g["d_tape"][layer] * d_scale)
            want = apply_update(p["g"][layer],
                                -lr * dw * p["w_scale"][layer], dev)
            np.testing.assert_allclose(n["g"][layer], want,
                                       rtol=1e-4, atol=1e-6)
            # and it actually moved
            assert float(jnp.max(jnp.abs(n["g"][layer]
                                         - p["g"][layer]))) > 0


def test_train_step_compiles_once_and_learns():
    """The jitted, donated analog train step must trace exactly once across
    steps (no-retrace guard, like the serve engine's decode step) and the
    loss must fall on the Markov stream."""
    cfg = _cfg()
    state = init_state(jax.random.PRNGKey(0), cfg)
    step = make_analog_sgd_step(cfg, lr=0.1)
    stream = make_token_stream(50_000, cfg.vocab, seed=0)
    key = jax.random.PRNGKey(1)
    losses = []
    for i in range(20):
        x, y = batch_tokens(stream, 8, 16, i)
        key, ks = jax.random.split(key)
        state, mets = step(state, {"tokens": jnp.asarray(x),
                                   "labels": jnp.asarray(y)}, ks)
        losses.append(float(mets["loss"]))
    assert step.compiles == 1
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < losses[0] - 0.3
    # conductances stay inside the physical window
    dev = crossbar_from_model(cfg).device
    g = state["params"]["layers"]["attn"]["wqkv"]["g"]
    assert float(g.min()) >= dev.gmin and float(g.max()) <= dev.gmax
    assert 0.0 <= float(mets["g_rail_frac"]) < 0.5
    # per-step hardware roll-up is attached and ordered sensibly
    pj = step.cost["pj_per_mac"]
    assert pj["analog"] < pj["digital_reram"] < pj["sram"]


def test_stochastic_device_requires_and_uses_key():
    """With write noise the same step and key reproduce; different keys
    diverge (the noise field feeds the Pallas kernel)."""
    cfg = _cfg(analog_device="taox")
    batch = _batch(cfg)

    def one(key):
        state = init_state(jax.random.PRNGKey(0), cfg)
        step = make_analog_sgd_step(cfg, lr=0.05)
        new, _ = step(state, batch, key)
        return new["params"]["layers"]["ffn"]["w_upgate"]["g"]

    a = one(jax.random.PRNGKey(3))
    b = one(jax.random.PRNGKey(3))
    c = one(jax.random.PRNGKey(4))
    np.testing.assert_array_equal(a, b)
    assert float(jnp.max(jnp.abs(a - c))) > 0
