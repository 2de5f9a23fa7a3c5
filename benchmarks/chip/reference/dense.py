"""Plain float32 reference of in-situ analog SGD on a dense decoder.

Written from the published layer equations and the device description
(paper arXiv:1707.09952 §III-§V), in straightforward ``jax.numpy``.  It
imports nothing of the system under test.  Every projection matrix lives
on a grid of ``rows x cols`` crossbar tiles:

* read (forward VMM, and the backward MVM through the same array): the
  drive is quantised to signed ``in_bits`` levels against the matrix's
  max |x|; each tile integrates ``x_int @ (g - ref)``; the tile's
  integrator range is ``sat_sigmas`` times the rms of its nonzero charges
  over the whole token block; the ramp ADC rounds the clipped charge to
  ``out_bits`` levels; tiles add digitally; the sum is rescaled by
  ``x_scale / w_scale``;
* write: the rows are quantised to ``in_bits`` levels and the columns
  (the backward error) to ``upd_col_bits`` levels, the rank-k outer
  product requests ``dg = -lr * w_scale * x_q^T d_q``, and the device
  answers with a state-dependent exponential-saturation slope (SET and
  RESET mirrored), random-walk write noise of ``write_noise * pulse_dg *
  sqrt(|dg| / pulse_dg)`` and clipping to the conductance window;
* embeddings, norm gains and an untied head are digital and take SGD.

The write noise is the device's counter-based generator, written out
from its description: per (layer, tile) a seed from four murmur3 fmix32
rounds, per cell pair a hashed counter, and a Box-Muller pair from the
two 16-bit halves of the hash, its legs on tile rows ``r`` and
``r + rows/2``.  The per-matrix seed mixes one uint32 drawn from the
step's key with the CRC-32 of the matrix's path.

``precision`` names the arithmetic of the analog matrix products:
``highest`` (float32), ``high`` (three bfloat16 passes) or ``default``
(one bfloat16 pass).  The two lower ones are emulated by splitting the
operands, so they mean the same on every backend.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high", "default")
STACK = "layers"


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _bf(x):
    """x rounded to bfloat16, kept in float32 (a rounding the compiler
    may not fold away, as it may a pair of casts)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def matmul(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32, or emulating the MXU's bfloat16
    passes (``high``: hi*hi + hi*lo + lo*hi; ``default``: hi*hi)."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = _bf(a), _bf(b)
    out = jnp.einsum(spec, a_hi, b_hi, precision=HIGHEST)
    if precision == "high":
        out = (out + jnp.einsum(spec, a_hi, _bf(b - b_hi), precision=HIGHEST)
               + jnp.einsum(spec, _bf(a - a_hi), b_hi, precision=HIGHEST))
    return out


def quantize(x, levels: int):
    """Symmetric mid-tread quantiser against the block's max |x|:
    returns (integer levels, scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / levels
    xi = jnp.clip(jnp.round(x / scale), -levels, levels)
    return xi, scale


# ---------------------------------------------------------------------------
# Crossbar read and write
# ---------------------------------------------------------------------------

def analog_read(x, g, ref, w_scale, dev: dict, transpose: bool,
                precision: str, with_lsb: bool = False):
    """One tiled crossbar read of a (K, N) array: x (T, K) -> (T, N), or
    with ``transpose`` x (T, N) -> (T, K) through the same tiles.  With
    ``with_lsb`` also returns each output's smallest ADC step over the
    tiles that feed it, in output units: the least by which one ADC code
    moves that output."""
    in_levels = 2 ** (dev["in_bits"] - 1) - 1
    out_levels = 2 ** (dev["out_bits"] - 1) - 1
    xi, x_scale = quantize(x, in_levels)
    diff = g - ref
    rows, cols = dev["rows"], dev["cols"]
    if transpose:
        diff, rows, cols = diff.T, cols, rows
    k, n = diff.shape
    tk, tn = -(-k // rows), -(-n // cols)
    diff = jnp.pad(diff, ((0, tk * rows - k), (0, tn * cols - n)))
    xi = jnp.pad(xi, ((0, 0), (0, tk * rows - k)))
    t = x.shape[0]
    q = matmul("btr,trnc->btnc", xi.reshape(t, tk, rows),
               diff.reshape(tk, rows, tn, cols), precision)
    sumsq = jnp.sum(q * q, axis=(0, 3), keepdims=True)
    nz = jnp.sum((q != 0).astype(jnp.float32), axis=(0, 3), keepdims=True)
    rms = jnp.sqrt(sumsq / jnp.maximum(nz, 1.0))
    sat = jnp.maximum(dev["sat_sigmas"] * rms, 1e-6)
    lsb = sat / out_levels
    code = jnp.clip(jnp.round(jnp.clip(q, -sat, sat) / lsb),
                    -out_levels, out_levels)
    y = jnp.sum(code * lsb, axis=1).reshape(t, tn * cols)[:, :n]
    y = y * (x_scale / w_scale)
    if not with_lsb:
        return y
    step = jnp.repeat(jnp.min(lsb[0, :, :, 0], axis=0), cols)[:n]
    return y, step * (x_scale / w_scale)


def update_operands(x, d, dev: dict):
    """Write-driver operands: rows on the temporal coder, columns on the
    voltage coder."""
    xi, xs = quantize(x, 2 ** (dev["in_bits"] - 1) - 1)
    di, ds = quantize(d, 2 ** (dev["upd_col_bits"] - 1) - 1)
    return xi * xs, di * ds


def make_analog_matmul(dev: dict, precision: str):
    """y = read(x) with a custom VJP: dx is the transpose read of the same
    array, the cotangent of ``g`` is the rank-k product x_q^T d_q that the
    write drivers apply (not a weight gradient), and that of ``mag`` (an
    array of g's shape for pulse-train writes, a scalar otherwise) is the
    drive activity |x_q|^T |d_q|."""

    @jax.custom_vjp
    def amm(x, g, ref, w_scale, mag):
        return analog_read(x, g, ref, w_scale, dev, False, precision)

    def fwd(x, g, ref, w_scale, mag):
        return amm(x, g, ref, w_scale, mag), (x, g, ref, w_scale, mag)

    def bwd(res, dy):
        x, g, ref, w_scale, mag = res
        dx = analog_read(dy, g, ref, w_scale, dev, True, precision)
        x_q, d_q = update_operands(x, dy, dev)
        acc = matmul("tk,tn->kn", x_q, d_q, precision)
        act = matmul("tk,tn->kn", jnp.abs(x_q), jnp.abs(d_q), precision) \
            if mag.ndim else jnp.zeros_like(mag)
        return dx, acc, jnp.zeros_like(ref), jnp.zeros_like(w_scale), act

    amm.defvjp(fwd, bwd)
    return amm


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def write_noise(seed, layer, shape, dev: dict):
    """Standard normals of one (K, N) array of a stack, from the matrix
    seed and the layer index (see the module docstring)."""
    k, n = shape
    rows, cols = dev["rows"], dev["cols"]
    if rows % 2:
        raise ValueError("the reference pairs tile rows; rows must be even")
    tk, tn = -(-k // rows), -(-n // cols)
    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)
    h = _fmix32(u32(seed) ^ jnp.uint32(0x9E3779B9))
    h = _fmix32(h + jnp.uint32(0x9E3779B1) * u32(layer))
    ik = jnp.arange(tk, dtype=jnp.uint32)[:, None]
    jn = jnp.arange(tn, dtype=jnp.uint32)[None, :]
    h = _fmix32(h + jnp.uint32(0x85EBCA77) * ik)
    h = _fmix32(h + jnp.uint32(0xC2B2AE3D) * jn)          # (tk, tn)
    half = rows // 2
    pid = (jnp.arange(half, dtype=jnp.uint32)[:, None] * jnp.uint32(cols)
           + jnp.arange(cols, dtype=jnp.uint32)[None, :])  # (half, cols)
    c = _fmix32(pid[None, None] ^ h[:, :, None, None])  # (tk, tn, half, cols)
    hi = (c >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (c & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    r = jnp.sqrt(-2.0 * jnp.log((hi + 1.0) * (1.0 / 65536)))
    a = (2.0 * np.pi) * (lo * (1.0 / 65536))
    z = jnp.concatenate([r * jnp.cos(a), r * jnp.sin(a)], axis=2)
    z = z.transpose(0, 2, 1, 3).reshape(tk * rows, tn * cols)
    return z[:k, :n]


def device_write(g, dg_req, noise, dev: dict):
    """The device's answer to a requested change ``dg_req``."""
    span = dev["gmax"] - dev["gmin"]
    x = (g - dev["gmin"]) / span

    def slope(u, nu):
        e = np.exp(-nu)
        mid = (np.exp(-0.5 * nu) - e) / (1.0 - e)
        return (jnp.exp(-nu * u) - e) / (1.0 - e) / mid

    up = dev["gain_set"] * slope(x, dev["nu_set"])
    dn = dev["gain_reset"] * slope(1.0 - x, dev["nu_reset"])
    dg = jnp.where(dg_req >= 0, dg_req * up, dg_req * dn)
    pulse = dev["pulse_dg"]
    dg = dg + dev["write_noise"] * pulse * jnp.sqrt(jnp.abs(dg_req) / pulse) \
        * noise
    return jnp.clip(g + dg, dev["gmin"], dev["gmax"])


def pulse_write(g, acc, act, m, noise, dev: dict):
    """A sign-decomposed pulse-train write: SET and RESET magnitudes
    (act|m| +- acc m)/2, each fired as a whole number of pulses through
    its own state-dependent slope, with the write noise of all pulses
    fired."""
    span = dev["gmax"] - dev["gmin"]
    x = (g - dev["gmin"]) / span

    def slope(u, nu):
        e = np.exp(-nu)
        mid = (np.exp(-0.5 * nu) - e) / (1.0 - e)
        return (jnp.exp(-nu * u) - e) / (1.0 - e) / mid

    pulse = dev["pulse_dg"]
    n_set = jnp.round(jnp.maximum(0.5 * (act * jnp.abs(m) + acc * m), 0.0)
                      / pulse)
    n_reset = jnp.round(jnp.maximum(0.5 * (act * jnp.abs(m) - acc * m), 0.0)
                        / pulse)
    dg = pulse * (n_set * dev["gain_set"] * slope(x, dev["nu_set"])
                  - n_reset * dev["gain_reset"] * slope(1.0 - x,
                                                        dev["nu_reset"]))
    dg = dg + dev["write_noise"] * pulse * jnp.sqrt(n_set + n_reset) * noise
    return jnp.clip(g + dg, dev["gmin"], dev["gmax"])


def carry_sweep(c, dev: dict, base: float):
    """Periodic carry: the carry array's signed value, read through the
    ADC at a full scale of half the window, moves into the primary array
    one significance level up, as far as the primary has room; both by
    exact closed-loop writes."""
    swing = 0.5 * (dev["gmax"] - dev["gmin"])
    levels = 2 ** (dev["out_bits"] - 1) - 1
    lsb = swing / levels
    v = jnp.clip(jnp.round((c["g_carry"] - c["ref"]) / lsb), -levels,
                 levels) * lsb
    head = swing - jnp.abs(c["g"] - c["ref"])
    t = jnp.clip(v, -head * base, head * base)
    return {**c,
            "g": jnp.clip(c["g"] + t / base, dev["gmin"], dev["gmax"]),
            "g_carry": jnp.clip(c["g_carry"] - t, dev["gmin"], dev["gmax"])}


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def is_container(p) -> bool:
    return isinstance(p, dict) and {"g", "ref", "w_scale"} <= set(p)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1, rotate-half layout."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """q: (B, S, H, D); k, v: (B, S, KVH, D); query head h reads kv head
    h // (H / KVH)."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    n = q.shape[1]
    mask = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def split(params, pulse: bool):
    """(trainable, fixed): ``g`` (whose cotangent is the write's product),
    for pulse trains ``mag`` (whose cotangent is the drive activity), and
    the digital leaves train; ``ref``, ``w_scale`` and ``g_carry`` are
    fixed."""
    if is_container(params):
        train = {"g": params["g"]}
        if pulse:
            train["mag"] = jnp.zeros_like(params["g"])
        return train, {k: v for k, v in params.items() if k != "g"}
    if isinstance(params, dict):
        parts = {k: split(v, pulse) for k, v in params.items()}
        return ({k: v[0] for k, v in parts.items()},
                {k: v[1] for k, v in parts.items()})
    return params, None


def merge(train, fixed):
    if fixed is None:
        return train
    if isinstance(fixed, dict) and "ref" in fixed:
        return {**fixed, **train}
    return {k: merge(train[k], fixed[k]) for k in train}


def block(x, lp, proj, model: dict):
    """One decoder layer; ``proj(name, container, x)`` applies a crossbar
    matrix to x (B, S, K)."""
    b, s = x.shape[:2]
    d = model["d_model"]
    hd = model["head_dim"] or d // model["n_heads"]
    nh, nkv = model["n_heads"], model["n_kv_heads"]
    eps = model["norm_eps"]
    act = jax.nn.gelu if model["act"] == "gelu" else jax.nn.silu
    a = lp["attn"]
    qkv = proj("wqkv", a["wqkv"], rmsnorm(x, lp["ln1"]["scale"], eps))
    q = qkv[..., :nh * hd].reshape(b, s, nh, hd)
    k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, s, nkv, hd)
    v = qkv[..., (nh + nkv) * hd:].reshape(b, s, nkv, hd)
    q, k = rope(q, model["rope_theta"]), rope(k, model["rope_theta"])
    o = causal_attention(q, k, v).reshape(b, s, nh * hd)
    x = x + proj("wo", a["wo"], o)
    f = lp["ffn"]
    h = rmsnorm(x, lp["ln2"]["scale"], eps)
    if model["gated"]:
        up, gate = jnp.split(proj("w_upgate", f["w_upgate"], h), 2, axis=-1)
        h = act(gate) * up
    else:
        h = act(proj("w_up", f["w_up"], h))
    return x + proj("w_down", f["w_down"], h)


def effective_g(c, model: dict):
    """The conductances a read sees: with a carry array, its signed
    deviation one significance level down is added."""
    if "g_carry" not in c:
        return c["g"]
    base = model.get("analog_carry_base", 4.0)
    return c["g"] + jax.lax.stop_gradient((c["g_carry"] - c["ref"]) / base)


def forward(params, tokens, model: dict, dev: dict, precision: str):
    """Logits (B, S, vocab) of the analog dense decoder."""
    amm = make_analog_matmul(dev, precision)
    b, s = tokens.shape
    d = model["d_model"]

    def proj(name, c, x):
        y = amm(x.reshape(b * s, -1), effective_g(c, model), c["ref"],
                c["w_scale"], c.get("mag", jnp.zeros((), jnp.float32)))
        return y.reshape(b, s, -1)

    x = params["embed"][tokens]
    # Layer by layer, recomputed in the backward pass, so that only the
    # layers' inputs are kept.
    x, _ = jax.lax.scan(jax.checkpoint(
        lambda x, lp: (block(x, lp, proj, model), None)), x, params[STACK])
    x = rmsnorm(x, params["final_ln"]["scale"], model["norm_eps"])
    if model["tie_embeddings"]:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                          precision=HIGHEST) / np.sqrt(d)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"],
                      precision=HIGHEST)


def first_layer_reads(params, tokens, model: dict, dev: dict):
    """Every crossbar read of the first layer in the forward pass of
    ``tokens`` (float32 reads), as {matrix: (drive (T, K), effective g,
    ref, w_scale)}; the drives are the reference's own."""
    b, s = tokens.shape
    first = jax.tree.map(lambda v: v[0], params[STACK])
    out = {}

    def proj(name, c, x):
        xt = x.reshape(b * s, -1)
        g = effective_g(c, model)
        out[name] = (xt, g, c["ref"], c["w_scale"])
        y = analog_read(xt, g, c["ref"], c["w_scale"], dev, False, "highest")
        return y.reshape(b, s, -1)

    block(params["embed"][tokens], first, proj, model)
    return out


def loss_fn(params, tokens, labels, model: dict, dev: dict, precision: str):
    """Mean next-token cross entropy."""
    logits = forward(params, tokens, model, dev, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true)


def _path_seed(base, path: str):
    return _fmix32(base ^ jnp.uint32(zlib.crc32(path.encode())))


def sgd_step(params, tokens, labels, key, step=0, *, model: dict,
             dev: dict, lr: float, precision: str = "highest"):
    """One analog-SGD step from the step counter ``step``: returns (new
    params, loss)."""
    pulse = model.get("analog_update_mode", "outer") == "pulse_train"
    base = model.get("analog_carry_base", 4.0)
    train, fixed = split(params, pulse)
    loss, grads = jax.value_and_grad(
        lambda t: loss_fn(merge(t, fixed), tokens, labels, model, dev,
                          precision))(train)
    seed_base = jax.random.bits(key, (), jnp.uint32)

    def write(c, gr, path):
        leaf = "g_carry" if "g_carry" in c else "g"
        g = c[leaf]
        seed = _path_seed(seed_base, path)
        scale = -lr * jnp.broadcast_to(c["w_scale"], g.shape[:-2])
        if leaf == "g_carry":
            scale = scale * base
        act = gr["mag"] if pulse else jnp.zeros(g.shape[:1])

        def one(args):
            g1, a1, m1, s1, layer = args
            z = write_noise(seed, layer, g1.shape, dev)
            if pulse:
                return pulse_write(g1, a1, m1, s1, z, dev)
            return device_write(g1, s1 * a1, z, dev)

        lyr = jnp.arange(g.shape[0], dtype=jnp.uint32)
        return {**c, leaf: jax.lax.map(one, (g, gr["g"], act, scale, lyr))}

    def walk(p, gr, path):
        if is_container(p):
            return write(p, gr, path)
        if isinstance(p, dict):
            return {k: walk(p[k], gr[k], f"{path}/{k}" if path else k)
                    for k in p}
        return p - lr * gr

    new = walk(params, grads, "")
    period = model.get("carry_period", 0)
    if model.get("analog_carry") and period > 0:
        def sweep(p):
            if is_container(p):
                return carry_sweep(p, dev, base)
            if isinstance(p, dict):
                return {k: sweep(v) for k, v in p.items()}
            return p
        new = jax.lax.cond((step + 1) % period == 0, sweep, lambda p: p, new)
    return new, loss


# ---------------------------------------------------------------------------
# Initial state, made from the seed by the benchmark
# ---------------------------------------------------------------------------

def _leaf_key(key, index: int):
    return jax.random.fold_in(key, index)


def init_group(key, path: tuple, spec, dev: dict):
    """Values of one parameter group (a container or a digital leaf),
    from its own key, in the layout ``spec`` (shapes) gives."""
    name = path[-1]
    if is_container(spec):
        shape = spec["g"].shape                      # (L, K, N)
        w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
            / np.sqrt(shape[-2])
        w_max = 8.0 * jnp.sqrt(jnp.mean(w * w, axis=(-2, -1)) + 1e-12)
        mid = 0.5 * (dev["gmin"] + dev["gmax"])
        swing = 0.5 * (dev["gmax"] - dev["gmin"])
        ws = swing / w_max
        g = mid + jnp.clip(w * ws[..., None, None], -swing, swing)
        out = {"g": g, "ref": jnp.full(shape, mid, jnp.float32),
               "w_scale": ws.astype(spec["w_scale"].dtype)}
        if "g_carry" in spec:
            out["g_carry"] = jnp.full(shape, mid, jnp.float32)
        return out
    shape, dtype = spec.shape, spec.dtype
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.zeros(shape, dtype)
    if name == "embed":
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name == "w":
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) \
            / np.sqrt(shape[-2])
    raise ValueError(f"no initial value rule for parameter {'/'.join(path)}")


def groups(spec, path=()):
    """[(path, spec)] of every parameter group, in sorted path order."""
    if is_container(spec) or not isinstance(spec, dict):
        return [(path, spec)]
    out = []
    for k in sorted(spec):
        out += groups(spec[k], path + (k,))
    return out


def _set(tree, path, value):
    if not path:
        return value
    return {**tree, path[0]: _set(tree.get(path[0], {}), path[1:], value)}


def make_state(key, spec, dev: dict):
    """A whole state with the program's layout, every group from its own
    ``fold_in`` of ``key``."""
    out = {}
    for i, (path, s) in enumerate(groups(spec)):
        out = _set(out, path, init_group(_leaf_key(key, i), path, s, dev))
    return out


def change_norms(key, state, spec, dev: dict):
    """{group path: ||now - initial||} over the trainable leaves (``g``
    and ``g_carry`` of a container, the whole digital leaf otherwise),
    the initial values made again from ``key``."""
    out = {}
    for i, (path, s) in enumerate(groups(spec)):
        if not is_container(s) and jnp.issubdtype(s.dtype, jnp.integer):
            continue
        cur = state
        for p in path:
            cur = cur[p]
        init = init_group(_leaf_key(key, i), path, s, dev)
        name = "/".join(path)
        if is_container(s):
            for leaf in ("g", "g_carry"):
                if leaf in s:
                    out[f"{name}/{leaf}"] = jnp.linalg.norm(
                        (cur[leaf] - init[leaf]).ravel())
        else:
            out[name] = jnp.linalg.norm((cur - init).ravel())
    return out
