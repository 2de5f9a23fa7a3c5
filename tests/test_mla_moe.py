"""DeepSeek-V2 (MLA with YaRN rope, a leading dense layer, held experts)
against the chip benchmark's plain reference, ``benchmarks/chip/
reference/mla_moe.py``, on seeded random weights at a small size with
the published ratios: 1 dense + 2 expert layers, 4 of 16 routed experts
held from expert 4, top-6, MLA's 2:1 nope:rope query-key split."""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import moe as MOE
from repro.models.layers import rope_table, yarn_freqs, yarn_mscale
from repro.train.analog_lm import init_state, make_analog_sgd_step

ROOT = Path(__file__).resolve().parents[1]
SMALL = dict(dtype="float32", n_layers=3, n_dense_layers=1, d_model=64,
             n_heads=4, n_kv_heads=4, d_ff=128, d_ff_expert=32, vocab=256,
             kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
             n_experts=4, n_routed_experts=16, first_expert=4, top_k=6)
DEVICE = dict(rows=32, cols=32, in_bits=8, out_bits=8, sat_sigmas=4.0,
              upd_col_bits=4, nu_set=5.0, nu_reset=5.0, gain_set=1.0,
              gain_reset=1.0, write_noise=0.3, pulse_dg=0.00390625,
              gmin=0.0, gmax=1.0)


@pytest.fixture(scope="module")
def ref():
    path = ROOT / "benchmarks" / "chip" / "reference" / "mla_moe.py"
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    return get_config("deepseek-v2-lite-16b").replace(**{**SMALL, **over})


def _model(cfg) -> dict:
    return {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}


def _batch(cfg, b=2, s=16, seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (b, s), 0, cfg.vocab),
            jax.random.randint(k2, (b, s), 0, cfg.vocab))


def test_yarn_table_matches_formula():
    """The published config's table (dim 64, factor 40 over 4096
    positions, beta 32 / 1, theta 10000) against DeepSeek-V2's formula
    written out in float64: float32 holds it to a few ulp."""
    cfg = get_config("deepseek-v2-lite-16b")
    dim, base, f = 64, 10000.0, 40.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                   dim - 1)
    extra = base ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / f * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(yarn_freqs(dim, cfg)), want,
                               rtol=1e-6)
    assert (low, high) == (10, 23)
    # cos/sin keep unit magnitude (mscale == mscale_all_dim); the softmax
    # scale takes mscale(40, 0.707)**2 = 1.2608**2
    freqs, mag = rope_table(dim, cfg)
    assert mag == 1.0
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)


def test_digital_forward_loss_and_grads_match_reference(ref):
    """The program's digital model (capacity of every token, so nothing
    drops) against the reference's layers driven by plain matmuls: the
    logits and loss agree to float32 rounding, and so does every
    gradient, to 1e-4 of its leaf's largest entry (the attention and the
    expert gathers add in other orders)."""
    cfg = _cfg(capacity_factor=16 / 6)
    model = _model(cfg)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tok, lab = _batch(cfg)
    proj = lambda name, c, x: x @ c["w"]
    eproj = lambda name, c, xe: jnp.einsum("etk,ekn->etn", xe, c)

    def ref_loss(p):
        x = p["embed"][tok]
        x = ref.dense_block(x, jax.tree.map(lambda v: v[0],
                                            p["dense_layers"]), proj, model)
        aux = 0.0
        for i in range(cfg.n_layers - cfg.n_dense_layers):
            x, a = ref.moe_block(x, jax.tree.map(lambda v: v[i],
                                                 p["layers"]),
                                 proj, eproj, model)
            aux = aux + a
        x = ref.rmsnorm(x, p["final_ln"]["scale"], cfg.norm_eps)
        logits = x @ p["lm_head"]["w"]
        ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lab[..., None], -1)[..., 0])
        return ce + 0.01 * aux, logits

    def prog_loss(p):
        loss, _ = M.loss_fn(p, {"tokens": tok, "labels": lab}, cfg)
        return loss, M.forward(p, {"tokens": tok}, cfg)[0]

    with jax.default_matmul_precision("highest"):
        (lr_, lg_r), g_r = jax.value_and_grad(ref_loss, has_aux=True)(params)
        (lp_, lg_p), g_p = jax.value_and_grad(prog_loss,
                                              has_aux=True)(params)
    np.testing.assert_allclose(float(lp_), float(lr_), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_r),
                               atol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_r)[0],
                            jax.tree.leaves(g_p)):
        scale = float(jnp.max(jnp.abs(a)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, \
            jax.tree_util.keystr(path)


def test_analog_step_matches_reference_in_interpret_mode(ref):
    """One analog SGD step through the program's step, both Pallas kernels
    in interpret mode, against the reference's step at float32.  The
    kernel's three bfloat16 passes form the same exact products as the
    reference's float32 einsum but add them in another order, so an ADC
    code whose charge lies within float32 rounding of a step boundary
    may round the other way: one of 127 steps of a 32-row tile's range
    moves the loss by about 1e-4 of itself, whence 5e-4.  A leaf's change
    may differ by such a code, or a DAC code of a backward error (amplified
    by the 4-bit column drivers); the changes agree to 2% on every leaf,
    far under what a lost expert, a dropped row or a wrong noise stream
    moves (the whole change of a leaf)."""
    cfg = _cfg(analog=True, analog_mode="device", analog_rows=32,
               analog_cols=32)
    model = _model(cfg)
    key = jax.random.PRNGKey(3)
    spec = jax.eval_shape(lambda k: init_state(k, cfg), key)
    state = jax.jit(lambda k: {**ref.make_state(k, spec, DEVICE),
                               "step": jnp.zeros((), jnp.int32)})(key)
    tok, lab = _batch(cfg)
    skey = jax.random.PRNGKey(11)
    with jax.default_matmul_precision("highest"):
        step = make_analog_sgd_step(cfg, lr=0.05, impl="interpret",
                                    read_impl="interpret")
        new, mets = step(jax.tree.map(jnp.copy, state),
                         {"tokens": tok, "labels": lab}, skey)
        ref_new, ref_loss = jax.jit(lambda p: ref.sgd_step(
            p, tok, lab, skey, 0, model=model, dev=DEVICE, lr=0.05))(
                jax.tree.map(jnp.copy, state["params"]))
        prog = ref.change_norms(key, new, spec, DEVICE)
        want = ref.change_norms(key, {"params": ref_new}, spec, DEVICE)
    assert int(mets["moe_dropped"]) == 0
    np.testing.assert_allclose(float(mets["loss"]), float(ref_loss),
                               rtol=5e-4)
    for leaf, w in want.items():
        assert float(w) > 0, leaf
        assert abs(float(prog[leaf]) - float(w)) <= 0.02 * float(w), leaf


def test_expert_shares_add_up_to_the_uncut_layer(ref):
    """Eight shards of a 16-expert layer, each holding 2 experts from
    expert 2i: the routed parts they give, plus the shared experts
    counted once, equal the uncut reference layer."""
    cfg = _cfg(n_experts=16, n_routed_experts=16, first_expert=0,
               capacity_factor=16 / 6)
    model = _model(cfg)
    p = MOE.moe_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    shared, _ = MOE.moe_layer({**p, "experts": jax.tree.map(
        jnp.zeros_like, p["experts"])}, x, cfg)
    total = shared
    for i in range(8):
        cut = cfg.replace(n_experts=2, first_expert=2 * i)
        part = {**p, "experts": jax.tree.map(lambda w: w[2 * i:2 * i + 2],
                                             p["experts"])}
        y, stats = MOE.moe_layer(part, x, cut)
        assert int(stats["moe_dropped"]) == 0
        total = total + (y - shared)
    proj = lambda name, c, v: v @ c["w"]
    eproj = lambda name, c, xe: jnp.einsum("etk,ekn->etn", xe, c)
    with jax.default_matmul_precision("highest"):
        routed, _ = ref.held_experts(x.reshape(-1, cfg.d_model), p, eproj,
                                     model)
        want = routed.reshape(x.shape) + ref.gated_mlp(x, p["shared"], proj)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
