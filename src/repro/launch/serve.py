"""Serving CLI: batched prefill+decode of a small model on synthetic
prompts (the production-scale decode path is exercised by the dry-run).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch lm100m --smoke \
        --scheduler static       # legacy whole-batch baseline
    PYTHONPATH=src python -m repro.launch.serve --arch lm100m --smoke \
        --backend analog --sim-days 3   # in-array decode + drift/recal

``--scheduler continuous`` (the default) runs the slot-based
continuous-batching scheduler; families without a per-slot positional
cache (ssm / hybrid / vlm / audio) fall back to the static path.
``--backend analog`` programs the weights onto tiled crossbars and
serves the conductances in-array (device-mode VMM decode), reporting
the arch-cost energy-per-token roll-up; ``--sim-days`` advances the
simulated deployment clock first, so retention drift and the scheduled
recalibration sweep are exercised.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config
from repro.models import model as M
from repro.serve import SamplingParams, make_engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=["digital", "analog"],
                    default="digital")
    ap.add_argument("--scheduler", "--engine", dest="scheduler",
                    choices=["continuous", "static"], default="continuous")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots for the continuous scheduler "
                         "(default: batch size)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--analog-device", default="taox-nonoise",
                    help="device model for --backend analog")
    ap.add_argument("--analog-tile", type=int, default=64,
                    help="sim tile size for --backend analog")
    ap.add_argument("--sim-days", type=float, default=0.0,
                    help="advance the analog backend's simulated clock "
                         "this many days before serving")
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # audit: allow RA301 -- entry point: persistent compile cache at a fixed path of the checkout (jax reads the env var itself when it is set)
        jax.config.update("jax_compilation_cache_dir", str(
            Path(__file__).resolve().parents[3] / ".jax_cache"))

    cfg = get_config(args.arch, smoke=args.smoke)
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.backend == "analog":
        cfg = cfg.replace(dtype="float32", analog=True,
                          analog_mode="device",
                          analog_device=args.analog_device,
                          analog_rows=args.analog_tile,
                          analog_cols=args.analog_tile)
        params = M.program_digital(params, cfg)
    rng = np.random.default_rng(args.seed)
    prompts = [list(rng.integers(0, cfg.vocab,
                                 size=rng.integers(4, args.prompt_len)))
               for _ in range(args.batch)]
    extras = {}
    if cfg.family == "vlm":
        extras["vision"] = jax.numpy.zeros(
            (args.batch, cfg.n_vision_tokens, cfg.d_model))
    if cfg.family == "audio":
        extras["audio"] = jax.numpy.zeros(
            (args.batch, cfg.n_audio_frames, cfg.d_model))
    engine = make_engine(cfg, params, backend=args.backend,
                         scheduler=args.scheduler,
                         max_len=args.prompt_len + args.max_new + 8,
                         extras=extras, n_slots=args.slots or args.batch,
                         prefill_chunk=args.prefill_chunk)
    sp = SamplingParams(temperature=args.temperature,
                        max_new_tokens=args.max_new)
    if args.sim_days:
        engine.advance_clock(args.sim_days * 86400.0)
    t0 = time.time()
    outs = engine.generate(prompts, sp, seed=args.seed)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    for i, o in enumerate(outs):
        print(f"[{i}] prompt={prompts[i][:8]}... -> {o[:16]}...")
    use_static = engine.scheduler == "static" \
        or not engine.supports_continuous
    mode = f"{engine.backend}/" + ("static" if use_static else "continuous")
    print(f"[{mode}] {n_tok} tokens in {dt:.2f}s = {n_tok / dt:.1f} tok/s")
    if not use_static:
        print(f"decode compiles={engine.decode_compiles} "
              f"metrics={dict(engine.metrics)}")
    if engine.backend == "analog":
        epj = engine.energy_per_token()
        print(f"maintenance={dict(engine.maintenance.metrics)}")
        print(f"energy/token: analog={epj['analog_pj']:.1f}pJ "
              f"digital_reram={epj['digital_reram_pj']:.1f}pJ "
              f"sram={epj['sram_pj']:.1f}pJ")
    return outs


if __name__ == "__main__":
    main()
