"""Analog vs numeric transformer training benchmark.

Trains the same small LM twice from identical initial weights:

  numeric — fp32 SGD on the digital model (the paper's "numeric" curve),
  analog  — in-situ on the simulated crossbars: forward=VMM, backward=MVM
            through the same conductances, rank-k parallel-write updates
            through the nonlinear device model (train/analog_lm.py).

Emits ``BENCH_analog_train.json`` with both loss curves, the projected
per-step energy / pJ-per-MAC on the analog, digital-ReRAM and SRAM cores
(hwmodel/arch_cost.train_step_cost), an ideal-device/high-bit forward
parity check against the digital model, the compile count of the jitted
step (must be 1), and warm-step throughput (tok/s + simulated GMAC/s).

``--configs a,b,c`` benchmarks several architectures in one run (the
registry makes every family train in situ — MoE expert stacks, SSD
in/out projections, hybrid shared blocks included); per-arch results land
under ``runs`` and a ``rows`` array (one ``{name, us_per_call,
sim_gmacs}`` row per arch) feeds the ``check_bench.py`` regression gate.

``--curve`` adds the paper's accuracy-vs-device-nonideality trade study
(docs/analog_pipeline.md §5): a write-noise sweep of the noisy ``taox``
device training {no-carry, carry, carry+pulse-train} variants at equal
steps, emitted under ``nonideality_curve`` together with two gate rows
(``analog_train/carry``, ``analog_train/pulse_train``) that
``check_bench --require`` pins.

``--mesh DxM`` runs the analog side sharded over a DATAxMODEL device mesh
(docs/analog_pipeline.md §Sharding); under ``JAX_PLATFORMS=cpu`` the
benchmark sets the host-platform device-count flag for you, so

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/analog_train_bench.py \
        --smoke --mesh 2x4

simulates 8 devices in one process.  The sharded run is bit-identical to
``--mesh 1x1`` by construction — the interesting outputs are the
throughput rows and the per-shard cost roll-up under ``cost["mesh"]``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _pre_init_mesh_flag(argv=None):
    """``--mesh`` on the CPU host needs the host device count set BEFORE
    jax initialises; peek at argv and extend XLA_FLAGS.  Only under
    ``JAX_PLATFORMS=cpu``: on an accelerator the mesh takes the real
    devices."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    argv = argv if argv is not None else sys.argv[1:]
    for i, a in enumerate(argv):
        mesh = None
        if a == "--mesh" and i + 1 < len(argv):
            mesh = argv[i + 1]
        elif a.startswith("--mesh="):
            mesh = a.split("=", 1)[1]
        elif a == "--mesh-sweep":
            mesh = "1x8"  # largest sweep shape; sets the device count
        if not mesh:
            continue
        n = 1
        for f in mesh.split("x"):
            n *= int(f)
        flags = os.environ.get("XLA_FLAGS", "")
        if n > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()


_pre_init_mesh_flag()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.synthetic import batch_tokens, make_token_stream
from repro.models import model as M
from repro.train import optimizer, train_loop
from repro.train.analog_lm import init_state, make_analog_sgd_step

Array = jax.Array


def bench_config(args, arch=None):
    base = get_config(arch or args.arch, smoke=args.smoke)
    kw = dict(dtype="float32", analog=True, analog_mode="device",
              analog_device=args.device,
              analog_in_bits=args.bits, analog_out_bits=args.bits)
    if args.smoke:
        # Small enough for CPU, big enough that the FFN spans several
        # physical tiles (the per-tile ADC boundary is the point).
        kw.update(analog_rows=64, analog_cols=64)
    if args.tile:
        # Explicit tile geometry — the --mesh scaling runs use 16x16 so
        # the smoke model's 64-wide projections split across shards
        # instead of degrading to replication.
        kw.update(analog_rows=args.tile, analog_cols=args.tile)
    return base.replace(**kw)


def sim_gmacs_per_step(cfg, n_tokens: int) -> float:
    """Simulated crossbar GMACs of one training step: VMM + MVM + OPU per
    projection (3 passes over the weight-stationary MACs)."""
    from repro.hwmodel.arch_cost import model_projections
    macs = sum(p.k * p.n * p.count * p.active
               for p in model_projections(cfg))
    return 3.0 * macs * n_tokens / 1e9


def run_analog(cfg, stream, args, mesh=None):
    state = init_state(jax.random.PRNGKey(args.seed), cfg)
    step = make_analog_sgd_step(cfg, lr=args.lr, mesh=mesh)
    if mesh is not None:
        state = step.shard_state(state)
    key = jax.random.PRNGKey(args.seed + 1)
    losses, step_walls, t0 = [], [], time.perf_counter()
    t_warm = None
    for i in range(args.steps):
        x, y = batch_tokens(stream, args.batch, args.seq, i)
        key, ks = jax.random.split(key)
        t_s = time.perf_counter()
        state, mets = step(state, {"tokens": jnp.asarray(x),
                                   "labels": jnp.asarray(y)}, ks)
        losses.append(float(mets["loss"]))  # sync point
        step_walls.append(time.perf_counter() - t_s)
        if i == 0:
            t_warm = time.perf_counter()  # compile + first step done
    wall = time.perf_counter() - t0
    # median warm step: robust to load spikes on shared runners (feeds
    # the check_bench regression row)
    warm = sorted(step_walls[1:]) or step_walls
    med_step = warm[len(warm) // 2]
    tok_step = args.batch * args.seq
    if args.steps >= 2:
        # warm throughput: exclude compile + first step
        warm_wall = max(time.perf_counter() - t_warm, 1e-9)
        warm_steps = args.steps - 1
    else:  # a single step has no warm window; report whole-run rates
        warm_wall, warm_steps = max(wall, 1e-9), args.steps
    return {"loss": losses, "wall_s": wall,
            "compiles": step.compiles, "cost": step.cost,
            "g_rail_frac": float(mets["g_rail_frac"]),
            "tok_per_s": warm_steps * tok_step / warm_wall,
            "median_step_us": med_step * 1e6,
            "sim_gmacs_per_s": warm_steps
            * sim_gmacs_per_step(cfg, tok_step) / warm_wall}


def run_numeric(cfg, stream, args):
    """Same model, same init weights, digital fp32 SGD."""
    dig = cfg.digital()
    opt = optimizer.sgd(args.lr)
    # identical init: program_linear round-trips dense_init exactly, so
    # reading the analog init back out reproduces the digital init.
    params = M.readout_digital(
        M.init_params(jax.random.PRNGKey(args.seed), cfg), cfg)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32), "err_fb": ()}
    step = jax.jit(train_loop.make_train_step(dig, opt),
                   donate_argnums=(0,))
    losses, step_walls, t0 = [], [], time.perf_counter()
    for i in range(args.steps):
        x, y = batch_tokens(stream, args.batch, args.seq, i)
        t_s = time.perf_counter()
        state, mets = step(state, {"tokens": jnp.asarray(x),
                                   "labels": jnp.asarray(y)})
        losses.append(float(mets["loss"]))
        step_walls.append(time.perf_counter() - t_s)
    warm = sorted(step_walls[1:]) or step_walls
    return {"loss": losses, "wall_s": time.perf_counter() - t0,
            "median_step_us": warm[len(warm) // 2] * 1e6}


def run_nonideality_curve(args, mesh=None):
    """Accuracy-vs-device-nonideality trade study (paper §V.C / §VI.B).

    Sweeps the write-noise multiplier of the noisy ``taox`` device
    (``taox:wn<mult>``, see core.tiled_analog.device_model) and trains
    three analog variants at every point from the same init and token
    stream, at equal steps:

      no_carry          — the plain single-array update path,
      carry             — periodic-carry LSB array (the paper's
                          accuracy-recovery mechanism: LSB writes are
                          amplified by carry_base, so the per-write SNR
                          doubles and the read path attenuates the
                          residual noise by 1/carry_base),
      carry_pulse_train — carry plus stochastic 4-phase pulse-train
                          writes, whose noise scales with the *total*
                          fired charge (the physically honest, noisier
                          write).

    The headline number is ``gap_closed_by_carry`` at the top noise
    point: the fraction of the (no_carry - numeric) final-loss gap the
    carry run recovers.  The acceptance contract pins it >= 0.5.
    """
    if args.curve_steps:
        args = argparse.Namespace(**{**vars(args),
                                     "steps": args.curve_steps})
    arch = (args.configs or args.arch).split(",")[0]
    base = bench_config(args, arch)
    variants = {
        "no_carry": {},
        "carry": dict(analog_carry=True, carry_period=args.carry_period,
                      analog_carry_base=args.carry_base),
        "carry_pulse_train": dict(analog_carry=True,
                                  carry_period=args.carry_period,
                                  analog_carry_base=args.carry_base,
                                  analog_update_mode="pulse_train"),
    }
    mults = [float(x) for x in args.curve_noise.split(",") if x]
    stream = make_token_stream(
        max(200_000, args.steps * args.batch * (args.seq + 1) + 1),
        base.vocab, seed=args.seed)
    tail = lambda ls: float(np.mean(ls[-5:]))  # noqa: E731
    numeric = run_numeric(base, stream, args)
    num_final = tail(numeric["loss"])
    points = []
    for m in mults:
        dev = f"taox:wn{m:g}" if m != 1.0 else "taox"
        pt = {"write_noise_mult": m, "device": dev}
        for vname, extra in variants.items():
            res = run_analog(base.replace(analog_device=dev, **extra),
                             stream, args, mesh=mesh)
            pt[vname] = {"final_loss": tail(res["loss"]),
                         "loss": thin_curve(res["loss"]),
                         "median_step_us": res["median_step_us"],
                         "compiles": res["compiles"]}
        gap = pt["no_carry"]["final_loss"] - num_final
        pt["gap_vs_numeric"] = gap
        pt["gap_closed_by_carry"] = (
            (pt["no_carry"]["final_loss"] - pt["carry"]["final_loss"])
            / gap if abs(gap) > 1e-9 else None)
        points.append(pt)
        print(f"curve wn x{m:g}: numeric={num_final:.4f} "
              f"no_carry={pt['no_carry']['final_loss']:.4f} "
              f"carry={pt['carry']['final_loss']:.4f} "
              f"carry+pulse={pt['carry_pulse_train']['final_loss']:.4f} "
              f"gap={gap:+.4f} closed="
              f"{pt['gap_closed_by_carry'] if pt['gap_closed_by_carry'] is not None else float('nan'):.2f}")
    top = points[-1]
    tok_step = args.batch * args.seq
    gmacs = sim_gmacs_per_step(base, tok_step)
    rows = [
        {"name": "analog_train/carry",
         "us_per_call": top["carry"]["median_step_us"],
         "sim_gmacs": gmacs},
        {"name": "analog_train/pulse_train",
         "us_per_call": top["carry_pulse_train"]["median_step_us"],
         "sim_gmacs": gmacs},
    ]
    return {
        "arch": base.name, "steps": args.steps, "lr": args.lr,
        "carry_period": args.carry_period, "carry_base": args.carry_base,
        "numeric_final_loss": num_final,
        "numeric_loss": thin_curve(numeric["loss"]),
        "points": points,
        "max_nonideality": {
            "write_noise_mult": top["write_noise_mult"],
            "gap_vs_numeric": top["gap_vs_numeric"],
            "gap_closed_by_carry": top["gap_closed_by_carry"],
        },
    }, rows


def run_mesh_point(cfg, stream, args, mesh, read_mode, steps):
    """One mesh-sweep point: AOT-compile the step once, read the compiled
    module's collective byte volume, then time warm steps with the same
    executable (so the HLO measured is exactly the HLO run)."""
    from repro.launch.hlo_analysis import (collective_byte_volume,
                                           count_collectives)
    state = init_state(jax.random.PRNGKey(args.seed), cfg)
    step = make_analog_sgd_step(cfg, lr=args.lr, mesh=mesh,
                                read_mode=read_mode)
    state = step.shard_state(state)
    x, y = batch_tokens(stream, args.batch, args.seq, 0)
    batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
    key = jax.random.PRNGKey(args.seed + 1)
    if mesh is not None and step._step is None:
        step._build_sharded_step(state, batch)
    compiled = step._step.lower(state, batch, key).compile()
    vol = collective_byte_volume(compiled.as_text())
    counts = count_collectives(compiled.as_text())
    walls, loss = [], float("nan")
    for i in range(steps):
        x, y = batch_tokens(stream, args.batch, args.seq, i)
        key, ks = jax.random.split(key)
        b = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
        t0 = time.perf_counter()
        state, mets = compiled(state, b, ks)
        loss = float(mets["loss"])  # sync point
        walls.append(time.perf_counter() - t0)
    warm = sorted(walls[1:]) or walls
    return {
        "median_step_us": warm[len(warm) // 2] * 1e6,
        "final_loss": loss,
        "gather_bytes_per_step": vol["total"],
        "collective_bytes_by_kind": {k: v for k, v in vol.items()
                                     if k != "total" and v},
        "collectives_per_step": counts["total"],
    }


MESH_SWEEP_SHAPES = ((1, 1), (2, 2), (2, 4), (1, 8))


def run_mesh_sweep(args):
    """Per-mesh-shape scaling rows for the exact-mode sharded step.

    For every shape in ``MESH_SWEEP_SHAPES`` the first arch trains a few
    warm steps with the default shard-local (manual-collective) read and
    records wall time plus ``gather_bytes_per_step`` — the compiled
    module's loop-multiplied collective byte volume
    (``launch.hlo_analysis.collective_byte_volume``).  Two A/B points
    quantify what the shard-local read buys:

      * the 2x4 point re-runs with the legacy gather-then-replay read
        (``read_mode="gather"``); the recorded ``byte_drop`` is the
        acceptance metric (parameter gathers vs activation partial sums,
        expected well beyond 4x),
      * the MoE arch repeats the pair at 2x4; its EP dispatch read must
        cut the gather volume at least ``n_experts``-fold.

    Emits one ``analog_train/mesh_DxM`` gate row per shape (pinned in CI
    via ``check_bench --require analog_train/mesh``).

    The sweep runs at its own small token batch
    (``--mesh-sweep-batch/--mesh-sweep-seq``, default 1x4): shard-local
    traffic is activation-sized (it scales with tokens) while gather-mode
    traffic is parameter-sized (it does not), so a token batch comparable
    to the smoke model's conductance blocks would blur exactly the scale
    separation the byte-drop metric exists to measure — the same
    reasoning behind the RA107 audit geometry.
    """
    from repro.launch.mesh import make_mesh
    arch = (args.configs or args.arch).split(",")[0]
    args = argparse.Namespace(**{**vars(args),
                                 "batch": args.mesh_sweep_batch,
                                 "seq": args.mesh_sweep_seq})
    cfg = bench_config(args, arch)
    if not args.tile:
        # The sweep needs the projections to actually split: 16x16
        # physical tiles, mirroring the CI mesh legs.
        cfg = cfg.replace(analog_rows=16, analog_cols=16)
    steps = args.mesh_sweep_steps
    stream = make_token_stream(
        max(200_000, steps * args.batch * (args.seq + 1) + 1),
        cfg.vocab, seed=args.seed)
    tok_step = args.batch * args.seq
    gmacs = sim_gmacs_per_step(cfg, tok_step)
    points, rows = [], []
    for d, m in MESH_SWEEP_SHAPES:
        mesh = make_mesh((d, m), ("data", "model")) if d * m > 1 else None
        pt = run_mesh_point(cfg, stream, args, mesh, "local", steps)
        pt = {"mesh": f"{d}x{m}", "devices": d * m, **pt}
        points.append(pt)
        rows.append({"name": f"analog_train/mesh_{d}x{m}",
                     "us_per_call": pt["median_step_us"],
                     "sim_gmacs": gmacs})
        print(f"mesh {d}x{m}: {pt['median_step_us']:.0f}us/step, "
              f"{pt['gather_bytes_per_step']} collective B/step")
    ref = run_mesh_point(cfg, stream, args, make_mesh((2, 4),
                                                      ("data", "model")),
                         "gather", steps)
    local_2x4 = next(p for p in points if p["mesh"] == "2x4")
    drop = ref["gather_bytes_per_step"] \
        / max(local_2x4["gather_bytes_per_step"], 1)
    print(f"mesh 2x4 [{arch}] byte drop local vs gather: "
          f"{ref['gather_bytes_per_step']} -> "
          f"{local_2x4['gather_bytes_per_step']} B/step ({drop:.1f}x)")

    # MoE EP: each shard reads only its own experts' tiles of the
    # replicated dispatch buffer; volume must drop >= n_experts-fold.
    moe_arch = "llama4-scout-17b-a16e"
    moe_cfg = bench_config(args, moe_arch)
    if not args.tile:
        moe_cfg = moe_cfg.replace(analog_rows=16, analog_cols=16)
    moe_stream = make_token_stream(
        max(200_000, steps * args.batch * (args.seq + 1) + 1),
        moe_cfg.vocab, seed=args.seed)
    moe_mesh = make_mesh((2, 4), ("data", "model"))
    moe = {mode: run_mesh_point(moe_cfg, moe_stream, args, moe_mesh,
                                mode, steps)
           for mode in ("local", "gather")}
    moe_drop = moe["gather"]["gather_bytes_per_step"] \
        / max(moe["local"]["gather_bytes_per_step"], 1)
    print(f"mesh 2x4 [{moe_arch}] EP byte drop: "
          f"{moe['gather']['gather_bytes_per_step']} -> "
          f"{moe['local']['gather_bytes_per_step']} B/step "
          f"({moe_drop:.1f}x, {moe_cfg.n_experts} experts)")
    return {
        "arch": cfg.name, "steps": steps,
        "batch": args.batch, "seq": args.seq,
        "tile": cfg.analog_rows,
        "points": points,
        "gather_mode_2x4": ref,
        "byte_drop_2x4": drop,
        "moe_ep": {"arch": moe_cfg.name,
                   "n_experts": moe_cfg.n_experts,
                   "local": moe["local"], "gather": moe["gather"],
                   "byte_drop": moe_drop},
    }, rows


def thin_curve(curve, cap=100):
    """Subsample a per-step loss curve for the JSON artifact (first and
    last point always kept).  At trajectory step counts the full curve is
    megabytes of noise; the artifact wants the shape, not every sample."""
    if len(curve) <= cap:
        return curve
    stride = -(-len(curve) // cap)
    out = curve[::stride]
    if out[-1] != curve[-1]:
        out.append(curve[-1])
    return out


def parity_check(cfg, args) -> float:
    """Max relative error of the ideal-device / high-bit analog forward
    against the digital forward on the same weights."""
    ideal = cfg.replace(analog_device="ideal", analog_in_bits=16,
                        analog_out_bits=16, analog_sat_sigmas=8.0)
    params = M.init_params(jax.random.PRNGKey(args.seed), ideal)
    dig = M.readout_digital(params, ideal)
    rng = np.random.default_rng(args.seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab, size=(args.batch, args.seq)), jnp.int32)}
    la, *_ = M.forward(params, batch, ideal)
    ld, *_ = M.forward(dig, batch, ideal.digital())
    return float(jnp.max(jnp.abs(la - ld)) / jnp.max(jnp.abs(ld)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=False)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--device", default="taox-nonoise",
                    help="ideal | taox | taox-nonoise | linearized")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL mesh for the sharded analog step, "
                         "e.g. 2x4 (CPU hosts get the device-count flag "
                         "set automatically)")
    ap.add_argument("--tile", type=int, default=0,
                    help="square physical tile size override "
                         "(0 = arch default / smoke 64)")
    ap.add_argument("--curve", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="also run the accuracy-vs-device-nonideality "
                         "curve (noisy taox x {no-carry, carry, "
                         "carry+pulse-train}) and emit it under "
                         "'nonideality_curve' plus analog_train/carry "
                         "and analog_train/pulse_train gate rows")
    ap.add_argument("--curve-steps", type=int, default=0,
                    help="step count for the --curve runs (0 = --steps); "
                         "lets a long-throughput main run keep the curve "
                         "at its calibrated short-sweep scale")
    ap.add_argument("--curve-noise", default="1,16,64",
                    help="comma-separated write-noise multipliers for "
                         "--curve (x-axis of the nonideality sweep)")
    ap.add_argument("--carry-period", type=int, default=4,
                    help="carry-sweep cadence for the --curve carry "
                         "variants")
    ap.add_argument("--carry-base", type=float, default=4.0,
                    help="significance ratio between the primary and "
                         "the carry LSB array for the --curve variants")
    ap.add_argument("--mesh-sweep", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="also run the per-mesh-shape scaling sweep "
                         "(1x1/2x2/2x4/1x8 shard-local read + 2x4 "
                         "gather-mode and MoE EP A/B byte-drop points); "
                         "emits 'mesh_sweep' plus analog_train/mesh_DxM "
                         "gate rows")
    ap.add_argument("--mesh-sweep-steps", type=int, default=6,
                    help="warm steps per --mesh-sweep point (kept small: "
                         "the sweep compiles 10 step variants)")
    ap.add_argument("--mesh-sweep-batch", type=int, default=1,
                    help="batch for the --mesh-sweep points (small, so "
                         "activation-sized partial sums stay well below "
                         "the smoke model's conductance blocks)")
    ap.add_argument("--mesh-sweep-seq", type=int, default=4,
                    help="sequence length for the --mesh-sweep points")
    ap.add_argument("--configs", default=None,
                    help="comma-separated arch list to benchmark in one "
                         "run (overrides --arch); per-arch results land "
                         "under 'runs' + check_bench-compatible 'rows'")
    ap.add_argument("--out", default="BENCH_analog_train.json")
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # A fixed path: the cache key includes it.  (jax reads the
        # environment variable itself when it is set.)
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    _pre_init_mesh_flag(argv)  # no-op unless argv was passed explicitly
    # Smoke-scale models don't need activation remat; it only inflates
    # compile time and recompute for BOTH runs (models/transformer._remat).
    # Respect an explicit REPRO_REMAT from the caller.
    os.environ.setdefault("REPRO_REMAT", "none")
    if args.steps is None:
        args.steps = 30 if args.smoke else 200
    if args.batch is None:
        args.batch = 8 if args.smoke else 32
    if args.seq is None:
        args.seq = 16 if args.smoke else 256

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = None
    if d * m > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))

    archs = [a for a in (args.configs or args.arch).split(",") if a]
    runs, rows = {}, []
    for arch in archs:
        cfg = bench_config(args, arch)
        stream = make_token_stream(
            max(200_000, args.steps * args.batch * (args.seq + 1) + 1),
            cfg.vocab, seed=args.seed)
        analog = run_analog(cfg, stream, args, mesh=mesh)
        numeric = run_numeric(cfg, stream, args)
        parity = parity_check(cfg, args)
        runs[arch] = {
            "arch": cfg.name, "family": cfg.family,
            "tok_per_s": analog["tok_per_s"],
            "sim_gmacs_per_s": analog["sim_gmacs_per_s"],
            "analog_loss": thin_curve(analog["loss"]),
            "numeric_loss": thin_curve(numeric["loss"]),
            "analog_wall_s": analog["wall_s"],
            "numeric_wall_s": numeric["wall_s"],
            # wall_ratio carries compile + steps; step_ratio is the warm
            # steady-state (median step over median step) — the number the
            # fused read path moves.
            "wall_ratio": analog["wall_s"] / numeric["wall_s"],
            "analog_step_us": analog["median_step_us"],
            "numeric_step_us": numeric["median_step_us"],
            "step_ratio": analog["median_step_us"]
            / numeric["median_step_us"],
            "analog_compiles": analog["compiles"],
            "g_rail_frac": analog["g_rail_frac"],
            "cost": analog["cost"],
            "pj_per_mac": analog["cost"]["pj_per_mac"],
            "parity_rel_err": parity,
        }
        tok_step = args.batch * args.seq
        rows.append({
            "name": f"analog_train_step_{cfg.name}",
            "us_per_call": analog["median_step_us"],
            "sim_gmacs": sim_gmacs_per_step(cfg, tok_step),
        })
        print(f"{cfg.name} analog[{args.device}/{args.bits}b, mesh "
              f"{args.mesh}]: loss {analog['loss'][0]:.3f} -> "
              f"{analog['loss'][-1]:.3f} ({analog['wall_s']:.1f}s, "
              f"compiles={analog['compiles']}, "
              f"{analog['tok_per_s']:.0f} tok/s, "
              f"{analog['sim_gmacs_per_s']:.2f} sim-GMAC/s)")
        print(f"{cfg.name} numeric:          loss "
              f"{numeric['loss'][0]:.3f} -> {numeric['loss'][-1]:.3f} "
              f"({numeric['wall_s']:.1f}s)")
        print(f"{cfg.name} analog/numeric: wall "
              f"{runs[arch]['wall_ratio']:.2f}x, warm step "
              f"{runs[arch]['step_ratio']:.2f}x")
        pj = analog["cost"]["pj_per_mac"]
        print("projected train energy, pJ/MAC: "
              + "  ".join(f"{k}={v:.3f}" for k, v in pj.items()))
        print(f"ideal/16-bit forward parity rel err: {parity:.2e}")

    curve = None
    if args.curve:
        curve, curve_rows = run_nonideality_curve(args, mesh=mesh)
        rows.extend(curve_rows)
        top = curve["max_nonideality"]
        closed = top["gap_closed_by_carry"]
        print(f"nonideality curve [{curve['arch']}]: at write-noise "
              f"x{top['write_noise_mult']:g} the carry run closes "
              f"{closed if closed is not None else float('nan'):.0%} of "
              f"the {top['gap_vs_numeric']:+.4f} analog/numeric gap")

    sweep = None
    if args.mesh_sweep:
        sweep, sweep_rows = run_mesh_sweep(args)
        rows.extend(sweep_rows)
        print(f"mesh sweep [{sweep['arch']}]: 2x4 collective bytes drop "
              f"{sweep['byte_drop_2x4']:.1f}x vs gather mode; MoE EP "
              f"{sweep['moe_ep']['byte_drop']:.1f}x "
              f"({sweep['moe_ep']['n_experts']} experts)")

    # legacy single-run layout at the top level (first arch) + runs/rows
    result = {
        "smoke": args.smoke, "device": args.device,
        "remat": os.environ.get("REPRO_REMAT", "full"),
        "mesh": args.mesh, "devices": d * m,
        "bits": args.bits, "steps": args.steps,
        "batch": args.batch, "seq": args.seq, "lr": args.lr,
        **runs[archs[0]],
        "runs": runs,
        "rows": rows,
        **({"nonideality_curve": curve} if curve else {}),
        **({"mesh_sweep": sweep} if sweep else {}),
        # Aggregate analog/numeric overhead across every benchmarked
        # family.  wall_ratio needs enough steps to amortise the compile
        # (~98% of a 10-step run is XLA, not training — see the CI
        # invocation's --steps); step_ratio is compile-free.
        "wall_ratio": sum(r["analog_wall_s"] for r in runs.values())
        / sum(r["numeric_wall_s"] for r in runs.values()),
        "step_ratio": sum(r["analog_step_us"] for r in runs.values())
        / sum(r["numeric_step_us"] for r in runs.values()),
    }
    print(f"aggregate analog/numeric: wall {result['wall_ratio']:.2f}x, "
          f"warm step {result['step_ratio']:.2f}x")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
