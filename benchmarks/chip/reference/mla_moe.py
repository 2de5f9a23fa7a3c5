"""Plain float32 reference of in-situ analog SGD on a DeepSeek-V2 decoder:
multi-head latent attention (MLA) with YaRN rope, leading dense layers,
then layers whose MLP is a routed mixture of experts with shared experts.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434,
and its released ``config.json``) and the device description, in
straightforward ``jax.numpy``; it imports nothing of the system under
test.  The crossbar read and write, the write noise, the state generator
and the change norms are ``dense.py``'s, loaded from its file, so that
each device equation is written once.

A layer, on input x (T tokens, width d), with every matrix below a
crossbar read (``dense.analog_read``, the backward pass the transpose
read of the same array and the write the rank-k product):

* attention: ``q = x Wq`` split per head into a 128-wide part and a
  64-wide rope part; ``[c, k_r] = x Wkv_a``, ``c`` (512 wide) normed;
  ``[k, v] = c Wkv_b`` per head; the rope parts rotated by the YaRN
  table (below); scores ``q.k * mscale**2 / sqrt(192)``, causal softmax;
  ``o Wo``;
* the first ``n_dense_layers`` layers: a gated SiLU MLP of ``d_ff``;
* the others: a router ``x R`` over ``n_routed_experts`` (digital),
  softmax, the greedy top-k (gates renormalised only with
  ``norm_topk_prob``), and the ``n_experts`` held experts
  ``first_expert ..``: held expert e reads the rows of the tokens that
  chose it, in token order, each through ``silu(x Wg) * (x Wu) Wd``,
  weighted by the token's gate for e; plus the shared experts, one gated
  MLP of ``n_shared_experts * d_ff_expert`` on every token.  What the
  experts held elsewhere would add is left out: it is computed on their
  chips;
* the loss: next-token cross entropy plus 0.01 times the sum over expert
  layers of ``width * sum_i f_i p_i`` (the fraction of top-1 choices and
  the mean router probability of each of the ``width`` experts).

YaRN (DeepSeek-V2's rotary embedding): inverse frequencies
``theta**(-2i/D)`` (extrapolated) and the same over ``factor``
(interpolated), mixed by a ramp that runs from 0 at dimension pair
``floor(D ln(ctx / (2 pi beta_fast)) / (2 ln theta))`` to 1 at
``ceil(D ln(ctx / (2 pi beta_slow)) / (2 ln theta))``; cos and sin
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
with ``mscale(s, m) = 0.1 m ln s + 1``.

Departures from the published model, each also the program's:

* rope rotates halves (x1, x2) where DeepSeek's code rotates interleaved
  pairs: a fixed permutation of the rope columns of Wq and Wkv_a;
* the balance loss is the Switch form above, not the published
  sequence-level one;
* the up and gate matrices of each MLP, dense, shared or a routed
  expert's, lie side by side on one grid ``w_upgate`` ([up | gate]);
* float32 throughout, where the published weights are bfloat16.

The write noise of held expert e of an expert stack of L layers, in
layer l, is drawn as layer ``(first_expert + e) * L + l`` of its
matrix: the layer index that expert would have in the whole layer's
stack, expert-major.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp


def _load_dense():
    path = Path(__file__).resolve().parent / "dense.py"
    spec = importlib.util.spec_from_file_location("reference_dense_of_mla",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _load_dense()
HIGHEST = dense.HIGHEST
analog_read = dense.analog_read
make_state = dense.make_state
change_norms = dense.change_norms
rmsnorm = dense.rmsnorm
EXPERT_STACK = "experts"


# ---------------------------------------------------------------------------
# Rope with YaRN
# ---------------------------------------------------------------------------

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_table(model: dict, dim: int):
    """(inverse frequencies (dim/2,), cos/sin magnitude) of the model's
    rope, YaRN where ``yarn_factor`` > 1."""
    theta = model["rope_theta"]
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    factor = model.get("yarn_factor", 0.0)
    if factor <= 1.0:
        return extra, 1.0
    ctx = model["yarn_original_max_pos"]
    low = math.floor(dim * math.log(ctx / (model["yarn_beta_fast"] * 2
                                           * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(ctx / (model["yarn_beta_slow"] * 2
                                           * math.pi))
                     / (2 * math.log(theta)))
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mag = _mscale(factor, model["yarn_mscale"]) \
        / _mscale(factor, model["yarn_mscale_all_dim"])
    return extra / factor * ramp + extra * (1.0 - ramp), mag


def rope(x, inv_freq, mag):
    """x: (B, S, H, D), positions 0..S-1, rotate-half layout."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * inv_freq[None, :]
    cos = mag * jnp.cos(ang)[None, :, None, :]
    sin = mag * jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(model: dict) -> float:
    qk = model["qk_nope_dim"] + model["qk_rope_dim"]
    scale = 1.0 / math.sqrt(qk)
    factor = model.get("yarn_factor", 0.0)
    if factor > 1.0 and model.get("yarn_mscale_all_dim"):
        scale *= _mscale(factor, model["yarn_mscale_all_dim"]) ** 2
    return scale


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def mla(x, a, proj, model: dict):
    """Multi-head latent attention over x (B, S, d), causal."""
    b, s = x.shape[:2]
    nh = model["n_heads"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], \
        model["v_head_dim"]
    r = model["kv_lora_rank"]
    inv, mag = rope_table(model, dr)
    q = proj("wq", a["wq"], x).reshape(b, s, nh, dn + dr)
    kv_a = proj("wkv_a", a["wkv_a"], x)
    c = rmsnorm(kv_a[..., :r], a["kv_norm"]["scale"], model["norm_eps"])
    kv = proj("wkv_b", a["wkv_b"], c).reshape(b, s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv, mag)], -1)
    k_r = rope(kv_a[..., None, r:], inv, mag)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, nh, dr))], -1)
    v = kv[..., dn:]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        * softmax_scale(model)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return proj("wo", a["wo"], o.reshape(b, s, nh * dv))


def gated_mlp(x, f, proj, prefix: str = ""):
    up, gate = jnp.split(proj(prefix + "w_upgate", f["w_upgate"], x), 2,
                         axis=-1)
    return proj(prefix + "w_down", f["w_down"], jax.nn.silu(gate) * up)


def dense_block(x, lp, proj, model: dict):
    eps = model["norm_eps"]
    x = x + mla(rmsnorm(x, lp["ln1"]["scale"], eps), lp["attn"], proj, model)
    return x + gated_mlp(rmsnorm(x, lp["ln2"]["scale"], eps), lp["ffn"], proj)


def route(h, router_w, model: dict):
    """(gates (E_held, T), routed mask (E_held, T), balance loss) of the
    held experts for tokens h (T, d)."""
    logits = jnp.einsum("td,de->te", h, router_w, precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, model["top_k"])
    if model.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    width = probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(top_i[:, 0], width), axis=0)
    aux = width * jnp.sum(jnp.mean(probs, axis=0) * f)
    held = model.get("first_expert", 0) + jnp.arange(model["n_experts"])
    chose = top_i[None, :, :] == held[:, None, None]      # (E, T, k)
    gates = jnp.sum(jnp.where(chose, top_p[None], 0.0), axis=-1)
    return gates, jnp.any(chose, axis=-1), aux


def held_experts(h, m, eproj, model: dict):
    """(the held experts' part of the output (T, d), balance loss) for
    tokens h (T, d); ``eproj(name, stack, xe)`` applies each held
    expert's matrix to its own rows xe (E, T, K).

    Held expert e is driven by the rows of the tokens routed to it, in
    token order, first in its T rows; the rest of its rows are zero."""
    gates, routed, aux = route(h, m["router"]["w"], model)
    order = jnp.argsort(~routed, axis=-1, stable=True)     # (E, T)
    filled = jnp.take_along_axis(routed, order, axis=-1)
    xe = jnp.where(filled[..., None], h[order], 0.0)        # (E, T, d)
    ex = m[EXPERT_STACK]
    up, gate = jnp.split(eproj("w_upgate", ex["w_upgate"], xe), 2, axis=-1)
    out = eproj("w_down", ex["w_down"], jax.nn.silu(gate) * up)
    # Back to token order, weighted by each token's gate for the expert.
    out = jnp.take_along_axis(out, jnp.argsort(order, axis=-1)[..., None],
                              axis=1)
    return jnp.sum(gates[..., None] * out, axis=0), aux


def moe_block(x, lp, proj, eproj, model: dict):
    """(x, balance loss) of one expert layer."""
    b, s, d = x.shape
    eps = model["norm_eps"]
    x = x + mla(rmsnorm(x, lp["ln1"]["scale"], eps), lp["attn"], proj, model)
    hs = rmsnorm(x, lp["ln2"]["scale"], eps)
    y, aux = held_experts(hs.reshape(b * s, d), lp["moe"], eproj, model)
    return x + y.reshape(b, s, d) \
        + gated_mlp(hs, lp["moe"]["shared"], proj, "shared/"), aux


def forward(params, tokens, model: dict, dev: dict, precision: str):
    """(logits (B, S, vocab), summed balance loss) of the analog model."""
    amm = dense.make_analog_matmul(dev, precision)
    b, s = tokens.shape

    def mag_of(c, lead=()):
        return c.get("mag", jnp.zeros(lead, jnp.float32))

    def proj(name, c, x):
        y = amm(x.reshape(b * s, -1), dense.effective_g(c, model), c["ref"],
                c["w_scale"], mag_of(c))
        return y.reshape(b, s, -1)

    def eproj(name, c, xe):
        e = c["g"].shape[0]
        return jax.vmap(amm)(xe, dense.effective_g(c, model), c["ref"],
                             c["w_scale"], mag_of(c, (e,)))

    x = params["embed"][tokens]
    # Layer by layer, recomputed in the backward pass, so that only the
    # layers' inputs are kept.
    x, _ = jax.lax.scan(jax.checkpoint(
        lambda x, lp: (dense_block(x, lp, proj, model), None)), x,
        params["dense_layers"])
    (x, aux), _ = jax.lax.scan(jax.checkpoint(
        lambda c, lp: ((lambda y: (y[0], c[1] + y[1]))(
            moe_block(c[0], lp, proj, eproj, model)), None)),
        (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rmsnorm(x, params["final_ln"]["scale"], model["norm_eps"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"],
                      precision=HIGHEST), aux


def first_layer_reads(params, tokens, model: dict, dev: dict):
    """Crossbar reads in the forward pass of ``tokens`` (float32 reads),
    as {matrix: (drive (T, K), effective g, ref, w_scale)}, the drives
    the reference's own: every matrix of the first dense layer, and of
    the first expert layer its attention, its shared MLP and the first
    held expert, driven by the rows routed to it (the rest zero)."""
    b, s = tokens.shape
    out = {}

    def reader(prefix):
        def proj(name, c, x):
            xt = x.reshape(b * s, -1)
            g = dense.effective_g(c, model)
            out[prefix + name] = (xt, g, c["ref"], c["w_scale"])
            y = analog_read(xt, g, c["ref"], c["w_scale"], dev, False,
                            "highest")
            return y.reshape(b, s, -1)
        return proj

    def eproj(name, c, xe):
        g = dense.effective_g(c, model)
        out["expert0/" + name] = (xe[0], g[0], c["ref"][0], c["w_scale"][0])
        return jax.vmap(lambda x1, g1, r1, w1: analog_read(
            x1, g1, r1, w1, dev, False, "highest"))(xe, g, c["ref"],
                                                    c["w_scale"])

    first = lambda stack: jax.tree.map(lambda v: v[0], params[stack])
    x = dense_block(params["embed"][tokens], first("dense_layers"),
                    reader("dense/"), model)
    moe_block(x, first("layers"), reader("moe_layer/"), eproj, model)
    return out


def loss_fn(params, tokens, labels, model: dict, dev: dict, precision: str):
    """Mean next-token cross entropy plus 0.01 times the balance loss."""
    logits, aux = forward(params, tokens, model, dev, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true) + 0.01 * aux


def sgd_step(params, tokens, labels, key, step=0, *, model: dict,
             dev: dict, lr: float, precision: str = "highest"):
    """One analog-SGD step from the step counter ``step``: returns (new
    params, loss)."""
    pulse = model.get("analog_update_mode", "outer") == "pulse_train"
    base = model.get("analog_carry_base", 4.0)
    train, fixed = dense.split(params, pulse)
    loss, grads = jax.value_and_grad(
        lambda t: loss_fn(dense.merge(t, fixed), tokens, labels, model, dev,
                          precision))(train)
    seed_base = jax.random.bits(key, (), jnp.uint32)

    def write(c, gr, path):
        leaf = "g_carry" if "g_carry" in c else "g"
        g = c[leaf]
        lead = g.shape[:-2]
        seed = dense._path_seed(seed_base, path)
        scale = -lr * jnp.broadcast_to(c["w_scale"], lead)
        if leaf == "g_carry":
            scale = scale * base
        act = gr["mag"] if pulse else jnp.zeros(lead)
        if EXPERT_STACK in path.split("/"):
            lyr, e = lead
            held = model.get("first_expert", 0) + jnp.arange(e)
            layer = held[None, :] * lyr + jnp.arange(lyr)[:, None]
        else:
            layer = jnp.arange(lead[0])
        layer = layer.astype(jnp.uint32)

        def one(args):
            g1, a1, m1, s1, idx = args
            z = dense.write_noise(seed, idx, g1.shape, dev)
            if pulse:
                return dense.pulse_write(g1, a1, m1, s1, z, dev)
            return dense.device_write(g1, s1 * a1, z, dev)

        flat = lambda v: v.reshape(-1, *v.shape[len(lead):])
        new = jax.lax.map(one, (flat(g), flat(gr["g"]), flat(act),
                                flat(scale), flat(layer)))
        return {**c, leaf: new.reshape(g.shape)}

    def walk(p, gr, path):
        if dense.is_container(p):
            return write(p, gr, path)
        if isinstance(p, dict):
            return {k: walk(p[k], gr[k], f"{path}/{k}" if path else k)
                    for k in p}
        return p - lr * gr

    new = walk(params, grads, "")
    period = model.get("carry_period", 0)
    if model.get("analog_carry") and period > 0:
        def sweep(p):
            if dense.is_container(p):
                return dense.carry_sweep(p, dev, base)
            if isinstance(p, dict):
                return {k: sweep(v) for k, v in p.items()}
            return p
        new = jax.lax.cond((step + 1) % period == 0, sweep, lambda p: p, new)
    return new, loss
