"""Training CLI: any --arch on synthetic tokens, with checkpoint/restart,
elastic re-sharding, optional analog-crossbar projection mode and int8
gradient compression.

    PYTHONPATH=src python -m repro.launch.train --arch lm100m --steps 200
    # kill it at any point, rerun the same command -> resumes from the
    # latest committed checkpoint (elastic: --mesh 1x1 / 2x2 / ... may
    # differ between runs).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.launch import sharding
from repro.launch.mesh import dp_axes, make_mesh
from repro.models.layers import set_shard_context
from repro.train import checkpoint, train_loop
from repro.train.optimizer import adamw


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 2x2 (needs host devices)")
    ap.add_argument("--analog", action="store_true",
                    help="run projections through the crossbar fake-quant")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # audit: allow RA301 -- entry point: persistent compile cache at a fixed path of the checkout (jax reads the env var itself when it is set)
        jax.config.update("jax_compilation_cache_dir", str(
            Path(__file__).resolve().parents[3] / ".jax_cache"))

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.analog:
        cfg = cfg.replace(analog=True)

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    set_shard_context(mesh, dp_axes(mesh))

    opt = adamw(args.lr)
    step_fn = train_loop.make_train_step(cfg, opt,
                                         grad_compress=args.grad_compress)

    pipe_cfg = PipelineConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                              global_batch=args.global_batch,
                              seed=args.seed)

    # --- init or resume ------------------------------------------------------
    abstract = train_loop.abstract_state(cfg, opt)
    p_sh = sharding.params_shardings(abstract["params"], cfg, mesh)
    state_sh = {
        "params": p_sh,
        "opt": {"m": p_sh, "v": p_sh, "t": sharding.replicated(mesh)},
        "step": sharding.replicated(mesh),
        "err_fb": (sharding.params_shardings(abstract["err_fb"], cfg, mesh)
                   if args.grad_compress else ()),
    }
    start_step = 0
    if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
        state = checkpoint.restore(args.ckpt_dir, abstract,
                                   shardings=state_sh)
        start_step = int(state["step"])
        print(f"resumed from step {start_step} (elastic mesh {args.mesh})")
    else:
        with mesh:
            # audit: allow RA304 -- zero-arg initializer; nothing to donate
            state = jax.jit(
                lambda: train_loop.init_state(
                    jax.random.PRNGKey(args.seed), cfg, opt),
                out_shardings=state_sh)()

    pipe = TokenPipeline(pipe_cfg, step=start_step)
    jit_step = jax.jit(step_fn, donate_argnums=(0,),
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None))

    t0 = time.time()
    with mesh:
        for i in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            state, metrics = jit_step(state, batch)
            if (i + 1) % args.log_every == 0 or i == start_step:
                print(f"step {i + 1:5d}  loss {float(metrics['loss']):.4f}"
                      f"  gnorm {float(metrics['grad_norm']):.3f}"
                      f"  ({(time.time() - t0):.1f}s)", flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, jax.device_get(state), i + 1)
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, jax.device_get(state), args.steps)
    print(f"done: {args.steps - start_step} steps in "
          f"{time.time() - t0:.1f}s")
    return state


if __name__ == "__main__":
    main()
