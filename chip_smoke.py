"""Smoke run of the in-situ analog training step on one TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # 2x2 mesh vs one chip

Drives the paper's main path — device-mode analog training of lm100m at
its published widths (12 layers, d_model 768, d_ff 3072, vocab 8192) on
1024x1024 tiles of the ``taox`` device with 8-bit DAC/ADC: forward = VMM,
backward = MVM, update = rank-k parallel write — through the normal entry
points, in one process, with random weights and synthetic tokens made
from ``--seed``.  Phases, in order; any failure exits non-zero:

  a. a TPU must be present: there is no CPU fallback;
  b. the compiled read (VMM, MVM) and update (outer, pulse_train) kernels
     against their fused jnp twins on one lm100m container (the wqkv
     stack).  A read fails when more than 1% of its outputs differ by
     more than one ADC LSB, an update when more than 1% of its cells
     differ by more than one write pulse;
  c. ``make_analog_sgd_step``: 4 steps at batch 8 x seq 256.  Requires one
     compile, finite losses, conductances that moved, and the Pallas
     kernels (``tpu_custom_call``) in the compiled step;
  d. ``serve.make_engine(backend="analog")`` on the trained conductances
     answers 4 requests of 16 new tokens with one decode compile.

``--four-chips`` runs only the sharded step: lm100m on a 2x2
(data, model) mesh with 384x384 tiles (a tile that splits every
container), against the same step on one of the chips from the same
seed.  The sharded read takes the jnp tile pipeline by design, so the two
runs are compared, not required to match.

The last line of stdout is one JSON object naming the device; nothing
after a failure prints it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
LR = 0.05
BATCH, SEQ, STEPS = 8, 256, 4
N_REQUESTS, NEW_TOKENS, PROMPT_LEN = 4, 16, 16


def log(*a):
    print(*a, flush=True)


def phase_device() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log(f"[a] device {info}")
    if dev.platform != "tpu":
        raise SystemExit(f"[a] FAIL: no TPU (platform {dev.platform!r})")
    return info


def lm100m(tile: int = 1024):
    from repro.configs import get_config
    return get_config("lm100m").replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_rows=tile, analog_cols=tile)


def _read_lsb(x, g, ref, w_scale, xcfg, transpose):
    """Per-output ADC LSB of a read, in output units: the largest
    ``sat / out_levels`` over the reduction tiles feeding that output,
    times the digital rescale (the tile ranges of the twin's program)."""
    from repro.core.adc import integrator_saturation, quantize_input
    from repro.core.crossbar import pad_to_tiles

    def one(x1, g1, r1, w1):
        x_int, x_scale = quantize_input(x1, xcfg.adc)
        diff = pad_to_tiles(g1 - r1, xcfg.rows, xcfg.cols)
        rows, cols = xcfg.rows, xcfg.cols
        if transpose:
            diff, rows, cols = diff.T, cols, rows
        kp, np_ = diff.shape
        x_int = jnp.pad(x_int, ((0, 0), (0, kp - x_int.shape[1])))
        q = jnp.einsum("btr,trnc->btnc",
                       x_int.reshape(-1, kp // rows, rows),
                       diff.reshape(kp // rows, rows, np_ // cols, cols),
                       precision=jax.lax.Precision.HIGHEST)
        _, sat = integrator_saturation(q, xcfg.adc, n_rows=rows,
                                       g_max=xcfg.device.gmax,
                                       reduce_axes=(0, 3))
        lsb = sat.max(axis=1)[0, :, 0] / xcfg.adc.out_levels
        out_dim = g1.shape[0] if transpose else g1.shape[1]
        return jnp.repeat(lsb * (x_scale / w1), cols)[:out_dim]

    return jax.vmap(one)(x, g, ref, w_scale)


def phase_kernels(cont: dict, xcfg, seed: int) -> None:
    """Compiled kernels vs fused twins on one full-width container."""
    from repro.core.xbar_ops import quantize_update_operands
    from repro.kernels.xbar_update import xbar_outer_update
    from repro.kernels.xbar_vmm import xbar_fused_read

    g, ref, ws = cont["g"], cont["ref"], cont["w_scale"]
    lyr, k, n = g.shape
    tokens = BATCH * SEQ
    kx, kd = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(kx, (lyr, tokens, k), jnp.float32)
    d = 0.05 * jax.random.normal(kd, (lyr, tokens, n), jnp.float32)
    bad = []
    for name, drive, transpose in (("vmm", x, False), ("mvm", d, True)):
        y_k = xbar_fused_read(drive, g, ref, ws, xcfg, transpose=transpose,
                              impl="pallas")
        y_t = xbar_fused_read(drive, g, ref, ws, xcfg, transpose=transpose,
                              impl="jnp")
        lsb = jax.jit(_read_lsb, static_argnums=(4, 5))(
            drive, g, ref, ws, xcfg, transpose)
        err = jnp.abs(y_k - y_t)
        share = float(jnp.mean(err > lsb[:, None, :]))
        log(f"[b] read {name} {tuple(y_k.shape)}: max|kernel-twin| "
            f"{float(jnp.max(err))!r}, max LSB {float(jnp.max(lsb))!r}, "
            f"share > 1 LSB {share!r}, finite "
            f"{bool(jnp.all(jnp.isfinite(y_k)))}")
        if share > 0.01 or not bool(jnp.all(jnp.isfinite(y_k))):
            bad.append(name)
    x_q, d_q = jax.vmap(lambda a, b: quantize_update_operands(a, b, xcfg)
                        )(x, d)
    scale = -LR * ws
    seed_u = jnp.uint32(seed + 7)
    pulse = xcfg.device.pulse_dg
    for mode in ("outer", "pulse_train"):
        outs = [xbar_outer_update(g, x_q, d_q, scale, xcfg, seed=seed_u,
                                  noise_mode="kernel", impl=impl,
                                  update_mode=mode)
                for impl in ("pallas", "fused")]
        err = jnp.abs(outs[0] - outs[1])
        share = float(jnp.mean(err > pulse))
        moved = float(jnp.max(jnp.abs(outs[0] - g)))
        log(f"[b] update {mode}: max|kernel-twin| {float(jnp.max(err))!r}, "
            f"share > 1 pulse {share!r}, max|dg| {moved!r}")
        if share > 0.01 or not moved > 0.0:
            bad.append(mode)
    if bad:
        raise SystemExit(f"[b] FAIL: kernel/twin disagreement in {bad}")


def _batches(cfg, seed: int):
    from repro.data.synthetic import batch_tokens, make_token_stream
    stream = make_token_stream(50_000, cfg.vocab, seed=seed)
    out = []
    for i in range(STEPS):
        x, y = batch_tokens(stream, BATCH, SEQ, i)
        out.append({"tokens": jnp.asarray(x), "labels": jnp.asarray(y)})
    return out


def _train(step, state, batches, seed: int):
    """Run the steps; returns (state, losses, warm step seconds)."""
    key = jax.random.PRNGKey(seed + 2)
    losses, times = [], []
    for batch in batches:
        key, ks = jax.random.split(key)
        t0 = time.perf_counter()
        state, mets = step(state, batch, ks)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        losses.append(float(mets["loss"]))
    return state, losses, times[1:]


def _containers(params, path=()):
    from repro.core.tiled_analog import is_analog_container
    if is_analog_container(params):
        return {"/".join(path): params}
    out = {}
    if isinstance(params, dict):
        for k, v in params.items():
            out.update(_containers(v, path + (k,)))
    return out


def phase_train(cfg, state, seed: int):
    from repro.train.analog_lm import make_analog_sgd_step
    step = make_analog_sgd_step(cfg, lr=LR)
    before = {p: jnp.copy(c["g"])
              for p, c in _containers(state["params"]).items()}
    state, losses, warm = _train(step, state, _batches(cfg, seed), seed)
    log(f"[c] losses {losses}")
    log(f"[c] warm step seconds {warm} (information only)")
    moved = {p: float(jnp.max(jnp.abs(c["g"] - before[p])))
             for p, c in _containers(state["params"]).items()}
    log(f"[c] max|dg| per container {moved}")
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (state, _batches(cfg, seed)[0], jax.random.PRNGKey(0)))
    hlo = step._step.lower(*shapes).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    log(f"[c] compiles {step.compiles}, tpu_custom_call ops {n_kernels}")
    fails = []
    if step.compiles != 1:
        fails.append(f"compiles={step.compiles}")
    if not np.isfinite(losses).all():
        fails.append("non-finite loss")
    if not all(v > 0.0 for v in moved.values()):
        fails.append("conductances did not move")
    if n_kernels == 0:
        fails.append("no Pallas kernel in the compiled step")
    if fails:
        raise SystemExit(f"[c] FAIL: {fails}")
    return state


def phase_serve(cfg, params, seed: int) -> None:
    from repro.serve import SamplingParams, make_engine
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (N_REQUESTS, PROMPT_LEN)).tolist()
    eng = make_engine(cfg, params, backend="analog",
                      max_len=PROMPT_LEN + NEW_TOKENS + 16)
    t0 = time.perf_counter()
    out = eng.generate(prompts, SamplingParams(max_new_tokens=NEW_TOKENS),
                       seed=seed)
    log(f"[d] {len(out)} requests in {time.perf_counter() - t0:.2f}s "
        f"(compile included), decode compiles {eng.decode_compiles}")
    log(f"[d] first request tokens {out[0]}")
    ok = (len(out) == N_REQUESTS
          and all(len(o) == NEW_TOKENS for o in out)
          and all(0 <= t < cfg.vocab for o in out for t in o)
          and eng.decode_compiles == 1)
    if not ok:
        raise SystemExit("[d] FAIL: wrong request count, length, token "
                         "range or decode compile count")


def phase_four_chips(seed: int) -> None:
    from repro.launch.mesh import make_mesh
    from repro.train.analog_lm import init_state, make_analog_sgd_step
    if len(jax.devices()) != 4:
        raise SystemExit(f"[4] FAIL: needs 4 chips, found "
                         f"{len(jax.devices())}")
    cfg = lm100m(tile=384)
    batches = _batches(cfg, seed)
    mesh = make_mesh((2, 2), ("data", "model"))
    sharded = make_analog_sgd_step(cfg, lr=LR, mesh=mesh)
    state = sharded.shard_state(init_state(jax.random.PRNGKey(seed), cfg))
    per_dev = {}
    for c in _containers(state["params"]).values():
        for leaf in c.values():
            for sh in leaf.addressable_shards:
                per_dev[str(sh.device)] = (per_dev.get(str(sh.device), 0)
                                           + sh.data.nbytes)
    log(f"[4] container bytes per device {per_dev}")
    state_m, loss_m, warm_m = _train(sharded, state, batches, seed)
    single = make_analog_sgd_step(cfg, lr=LR)
    state_1, loss_1, warm_1 = _train(
        single, init_state(jax.random.PRNGKey(seed), cfg), batches, seed)
    log(f"[4] losses 2x2 mesh {loss_m}")
    log(f"[4] losses one chip {loss_1}")
    log(f"[4] warm step seconds 2x2 {warm_m}, one chip {warm_1} "
        f"(information only)")
    one = jax.devices()[0]
    cm = _containers(state_m["params"])
    dg = {p: float(jnp.max(jnp.abs(jax.device_put(cm[p]["g"], one)
                                   - c["g"])))
          for p, c in _containers(state_1["params"]).items()}
    log(f"[4] max|g_mesh - g_one| per container {dg}")
    log(f"[4] compiles mesh {sharded.compiles}, one chip {single.compiles}")
    if not (np.isfinite(loss_m).all() and np.isfinite(loss_1).all()
            and sharded.compiles == 1 and single.compiles == 1):
        raise SystemExit("[4] FAIL: non-finite loss or retrace")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh step against one chip")
    args = ap.parse_args(argv)
    info = phase_device()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # A fixed path: the cache key includes it.  (jax reads the
        # environment variable itself when it is set.)
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        from repro.core.tiled_analog import crossbar_from_model
        from repro.train.analog_lm import init_state
        cfg = lm100m()
        state = init_state(jax.random.PRNGKey(args.seed), cfg)
        phase_kernels(state["params"]["layers"]["attn"]["wqkv"],
                      crossbar_from_model(cfg), args.seed)
        state = phase_train(cfg, state, args.seed)
        phase_serve(cfg, state["params"], args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
