"""Family-agnostic analog registry: routing, expert-batched updates,
shared-block tapes, and device-mode coverage of every registered config."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import analog_registry as reg
from repro.core import apply_update
from repro.core.tiled_analog import (crossbar_from_model,
                                     is_analog_container, with_tapes)
from repro.models import model as M
from repro.train.analog_lm import init_state, make_analog_sgd_step


def _cfg(name, **kw):
    base = dict(dtype="float32", analog=True, analog_mode="device",
                analog_device="taox-nonoise", analog_rows=16,
                analog_cols=16, analog_in_bits=8, analog_out_bits=8)
    base.update(kw)
    return get_config(name, smoke=True).replace(**base)


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_vision_tokens, cfg.d_model)),
            jnp.float32)
    if cfg.family == "audio":
        batch["audio"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_audio_frames, cfg.d_model)),
            jnp.float32)
    return batch


# ------------------------------------------------------------ classification

def test_classify_consumer_kinds():
    assert reg.classify(("layers", "attn", "wqkv")) == reg.COLUMN_PARALLEL
    assert reg.classify(("layers", "attn", "wo", "g")) == reg.ROW_PARALLEL
    assert reg.classify(("layers", "ssm", "in_proj")) == reg.COLUMN_PARALLEL
    assert reg.classify(("layers", "ssm", "out_proj")) == reg.ROW_PARALLEL
    # expert stacks win over the per-matrix orientation
    assert reg.classify(("layers", "moe", "experts", "w_down")) \
        == reg.EXPERT_BATCHED
    assert reg.classify(("layers", "moe", "experts", "w_up", "x_tape")) \
        == reg.EXPERT_BATCHED
    # shared MoE experts are ordinary wide FFNs
    assert reg.classify(("layers", "moe", "shared", "w_upgate")) \
        == reg.COLUMN_PARALLEL


def test_classify_param_triage():
    assert reg.classify_param(("embed",)) == "digital"
    assert reg.classify_param(("lm_head", "w")) == "digital"
    assert reg.classify_param(("layers", "moe", "router", "w")) == "digital"
    assert reg.classify_param(("layers", "ln1", "scale")) == "digital"
    assert reg.classify_param(("layers", "ssm", "conv_w")) == "digital"
    assert reg.classify_param(("layers", "attn", "wqkv", "w")) \
        == reg.COLUMN_PARALLEL
    assert reg.classify_param(("layers", "moe", "experts", "w_up")) \
        == reg.EXPERT_BATCHED
    # a matrix the registry cannot place is None — never silently digital
    assert reg.classify_param(("layers", "mystery_proj", "w")) is None


def test_tape_routes():
    cfg = _cfg("llama4-scout-17b-a16e")
    # device-mode dispatch is dropless: an expert's tape holds every token
    assert reg.tape_lead(("layers", "moe", "experts", "w_up"), cfg, 64) \
        == (64,)
    assert reg.tape_lead(("layers", "attn", "wqkv"), cfg, 64) == (64,)
    hy = _cfg("zamba2-1.2b")
    groups = hy.n_layers // hy.attn_every
    assert reg.tape_reps(("shared_attn", "wqkv"), hy) == groups
    assert reg.tape_lead(("shared_ffn", "w_upgate"), hy, 64) == (groups, 64)


def test_flatten_lead_expert_roundtrip():
    """(L, E, K, N) flattens expert-outermost onto the kernel's layer axis
    and unflattens back exactly."""
    lyr, e, k, n, t = 3, 4, 8, 10, 6
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    g = jax.random.normal(key[0], (lyr, e, k, n))
    x = jax.random.normal(key[1], (lyr, e, t, k))
    d = jax.random.normal(key[2], (lyr, e, t, n))
    s = jax.random.normal(key[3], (lyr, e))
    g3, x3, d3, s1, _, unflatten = reg.flatten_lead(
        reg.EXPERT_BATCHED, g, x, d, s)
    assert g3.shape == (lyr * e, k, n)
    assert x3.shape == (lyr * e, t, k) and s1.shape == (lyr * e,)
    # expert-major: flattened row i = expert i // L, layer i % L
    np.testing.assert_array_equal(g3[2 * lyr + 1], g[1, 2])
    np.testing.assert_array_equal(s1[2 * lyr + 1], s[1, 2])
    np.testing.assert_array_equal(unflatten(g3), g)


def test_flatten_lead_reps_collapse():
    """A 2-D container applied G times (hybrid shared block) collapses its
    per-application tape dim into the token contraction."""
    k, n, g_reps, t = 8, 6, 3, 5
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    g = jax.random.normal(keys[0], (k, n))
    x = jax.random.normal(keys[1], (g_reps, t, k))
    d = jax.random.normal(keys[2], (g_reps, t, n))
    g2, x2, d2, s, _, unflatten = reg.flatten_lead(
        reg.COLUMN_PARALLEL, g, x, d, jnp.float32(0.5))
    assert g2.shape == (k, n) and x2.shape == (g_reps * t, k)
    np.testing.assert_array_equal(unflatten(g2), g)
    # summed outer product over applications is preserved
    np.testing.assert_allclose(
        np.einsum("bk,bn->kn", x2, d2),
        np.einsum("gtk,gtn->kn", x, d), rtol=1e-6)


# ------------------------------------------------- expert-batched correctness

def test_expert_update_matches_per_expert_reference():
    """One analog step moves every EXPERT's conductances by its own Fig.
    3c rank-k write: outer(x_q, d_q) over its dispatch rows, through the
    nonlinear device model — same contract the dense containers have."""
    cfg = _cfg("llama4-scout-17b-a16e")
    lr = 0.05
    state = init_state(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(jnp.copy, state["params"])
    batch = _batch(cfg, b=4, s=16)
    n_tokens = batch["tokens"].size

    tokens_for = lambda path, shape: reg.tape_lead(path, cfg, n_tokens, batch["tokens"].shape)
    _, grads = jax.value_and_grad(M.loss_fn, has_aux=True)(
        with_tapes(params, n_tokens, tokens_for=tokens_for), batch, cfg)

    step = make_analog_sgd_step(cfg, lr=lr)
    new_state, _ = step(state, batch, jax.random.PRNGKey(9))

    dev = crossbar_from_model(cfg).device
    p = params["layers"]["moe"]["experts"]["w_upgate"]
    t = grads["layers"]["moe"]["experts"]["w_upgate"]
    nw = new_state["params"]["layers"]["moe"]["experts"]["w_upgate"]
    moved = 0
    for layer in range(p["g"].shape[0]):
        for ex in range(p["g"].shape[1]):
            dw = jnp.einsum("bk,bn->kn", t["x_tape"][layer, ex],
                            t["d_tape"][layer, ex])
            want = apply_update(p["g"][layer, ex],
                                -lr * dw * p["w_scale"][layer, ex], dev)
            np.testing.assert_allclose(nw["g"][layer, ex], want,
                                       rtol=1e-4, atol=1e-6)
            moved += float(jnp.max(jnp.abs(nw["g"][layer, ex]
                                           - p["g"][layer, ex]))) > 0
    # routed experts actually received updates this step
    assert moved >= p["g"].shape[0]  # at least one expert per layer


def test_shared_block_tapes_one_slot_per_application():
    """Hybrid (zamba2): the shared attention block is ONE weight set
    applied at every group boundary; its containers tape one operand block
    per application and the summed outer product drives the write."""
    cfg = _cfg("zamba2-1.2b")
    lr = 0.05
    groups = cfg.n_layers // cfg.attn_every
    state = init_state(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(jnp.copy, state["params"])
    batch = _batch(cfg, b=2, s=16)
    n_tokens = batch["tokens"].size

    tokens_for = lambda path, shape: reg.tape_lead(path, cfg, n_tokens, batch["tokens"].shape)
    _, grads = jax.value_and_grad(M.loss_fn, has_aux=True)(
        with_tapes(params, n_tokens, tokens_for=tokens_for), batch, cfg)
    t = grads["shared_attn"]["wqkv"]
    assert t["x_tape"].shape[0] == groups
    # distinct applications deposit distinct operands
    assert float(jnp.max(jnp.abs(t["x_tape"][0] - t["x_tape"][1]))) > 0

    step = make_analog_sgd_step(cfg, lr=lr)
    new_state, _ = step(state, batch, jax.random.PRNGKey(9))
    p = params["shared_attn"]["wqkv"]
    dev = crossbar_from_model(cfg).device
    dw = jnp.einsum("gtk,gtn->kn", t["x_tape"], t["d_tape"])
    want = apply_update(p["g"], -lr * dw * p["w_scale"], dev)
    np.testing.assert_allclose(new_state["params"]["shared_attn"]["wqkv"]["g"],
                               want, rtol=1e-4, atol=1e-6)


def test_fused_cross_attention_single_container():
    """VLM cross-attention: one wqkv container per cross block (no split
    wq/wk/wv chains), applied once per step — the tapes carry both token
    streams."""
    cfg = _cfg("llama-3.2-vision-90b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    xattn = params["cross_layers"]["xattn"]
    assert set(xattn) == {"wqkv", "wo"}
    assert is_analog_container(xattn["wqkv"])
    batch = _batch(cfg)
    n_tok = batch["tokens"].size
    tokens_for = lambda path, shape: reg.tape_lead(path, cfg, n_tok, batch["tokens"].shape)
    _, grads = jax.value_and_grad(M.loss_fn, has_aux=True)(
        with_tapes(params, n_tok, tokens_for=tokens_for), batch, cfg)
    t = grads["cross_layers"]["xattn"]["wqkv"]
    b, s = batch["tokens"].shape
    # operand rows = decoder tokens + vision tokens, per cross block
    assert t["x_tape"].shape[-2] == b * (s + cfg.n_vision_tokens)


# ----------------------------------------------- whole-zoo device-mode pass

@pytest.mark.parametrize("name", sorted(ARCHS))
def test_every_config_trains_one_device_step(name):
    """Acceptance: every registered config init-and-one-steps under
    analog_mode="device" — no analog=False fallback, no
    unsupported-family error — and the registry audit passes."""
    cfg = _cfg(name)
    state = init_state(jax.random.PRNGKey(0), cfg)
    step = make_analog_sgd_step(cfg, lr=0.05)
    batch = _batch(cfg)
    state, mets = step(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(mets["loss"]))
    assert 0.0 <= float(mets["g_rail_frac"]) < 1.0


# ---------------------------------------------------- analog/numeric parity

@pytest.mark.parametrize("name", ["llama4-scout-17b-a16e", "mamba2-1.3b"])
def test_moe_ssm_analog_numeric_loss_parity(name):
    """With an ideal device and 16-bit I/O the device-mode loss matches
    the digital loss of the serially-read-out weights at rtol 1e-2 — the
    same parity contract the dense family has."""
    cfg = _cfg(name, analog_device="ideal", analog_in_bits=16,
               analog_out_bits=16, analog_sat_sigmas=8.0)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    digital = M.readout_digital(params, cfg)
    batch = _batch(cfg, b=4, s=16)
    la, _ = M.loss_fn(params, batch, cfg)
    ld, _ = M.loss_fn(digital, batch, cfg.digital())
    np.testing.assert_allclose(float(la), float(ld), rtol=1e-2)


def test_validate_device_params_catches_digital_projection():
    cfg = _cfg("lm100m")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # sabotage: replace a container with a digital weight dict
    params["layers"]["ffn"]["w_down"] = {
        "w": jnp.zeros((cfg.d_ff, cfg.d_model))}
    with pytest.raises(ValueError, match="w_down"):
        reg.validate_device_params(params, cfg)


# ------------------------------------------------------- MoE fakequant QAT

def test_expert_project_fakequant_matches_dense_reference():
    """Fakequant ``expert_project`` equals the per-expert
    ``fakequant_project`` reference exactly, engages at 8-bit I/O, and
    converges to the digital einsum as the bit depth grows."""
    from repro.core import AdcConfig
    from repro.kernels.ops import fakequant_project
    from repro.models.layers import expert_project
    cfg = _cfg("llama4-scout-17b-a16e", analog_mode="fakequant")
    rng = np.random.default_rng(0)
    e, t, k, n = 4, 8, 24, 12
    w = jnp.asarray(rng.normal(size=(e, k, n)) * 0.2, jnp.float32)
    x = jnp.asarray(rng.normal(size=(e, t, k)), jnp.float32)
    y = expert_project(w, x, cfg)
    adc = AdcConfig(in_bits=cfg.analog_in_bits,
                    out_bits=cfg.analog_out_bits)
    ref = jnp.stack([fakequant_project(x[i], w[i], adc, cfg.analog_rows,
                                       impl="jnp") for i in range(e)])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    y_dig = expert_project(w, x, cfg.digital())
    assert float(jnp.abs(y - y_dig).max()) > 0.0  # 8-bit I/O quantises
    hi = cfg.replace(analog_in_bits=16, analog_out_bits=16,
                     analog_sat_sigmas=8.0)
    y16 = expert_project(w, x, hi)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y_dig),
                               rtol=1e-2, atol=5e-3)


def test_moe_fakequant_loss_parity_and_grad():
    """16-bit fakequant MoE loss matches the digital loss at rtol 1e-2 —
    the dense-family QAT parity contract now covers the expert einsums —
    and the fake-quant graph stays differentiable through the experts."""
    cfg = get_config("llama4-scout-17b-a16e", smoke=True).replace(
        dtype="float32", analog=True, analog_mode="fakequant",
        analog_rows=16, analog_cols=16, analog_in_bits=16,
        analog_out_bits=16, analog_sat_sigmas=8.0)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, b=4, s=16)
    lq, _ = M.loss_fn(params, batch, cfg)
    ld, _ = M.loss_fn(params, batch, cfg.digital())
    np.testing.assert_allclose(float(lq), float(ld), rtol=1e-2)
    g = jax.grad(lambda p: M.loss_fn(p, batch, cfg)[0])(params)
    gw = g["layers"]["moe"]["experts"]["w_upgate"]
    assert float(jnp.abs(gw).max()) > 0.0


def test_moe_grouped_dispatch_fakequant_engages():
    """The K4-explicit grouped dispatch threads the same fake-quant
    through its expert projections: 16-bit matches grouped-digital,
    8-bit visibly quantises."""
    from repro.models import moe as MOE
    cfg = get_config("llama4-scout-17b-a16e", smoke=True).replace(
        dtype="float32", analog=True, analog_mode="fakequant",
        analog_rows=16, analog_cols=16, analog_in_bits=16,
        analog_out_bits=16, analog_sat_sigmas=8.0)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)), jnp.float32)
    y16, _ = MOE._moe_apply_grouped(p, x, cfg, groups=2)
    yd, _ = MOE._moe_apply_grouped(p, x, cfg.digital(), groups=2)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(yd),
                               rtol=2e-2, atol=5e-3)
    y8, _ = MOE._moe_apply_grouped(
        p, x, cfg.replace(analog_in_bits=8, analog_out_bits=8), groups=2)
    assert float(jnp.abs(y8 - yd).max()) > 0.0
