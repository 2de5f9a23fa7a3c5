"""Driver of ``train`` traffic: in-situ analog SGD through the program's
own step, ``repro.train.analog_lm.make_analog_sgd_step(cfg, lr)``, called
as ``step(state, batch, key)``.

Set-up: the initial state in the program's layout, made on the device
from the seed by the reference module in one jitted call; the step
object; a pool of distinct token batches from the seed; then the first
three steps through that same step object and feed, which compile the
step and give the readings the correctness check compares.  The window
then runs the same object on, one step in flight while the host waits
for the one before, for ``--seconds``; every step's tokens and time
count.  With ``--trace 1`` the window runs under the profiler instead
and the trace is reduced to the per-layer metrics.

Correctness, once the window has closed and the program's state is
freed: the reference (``reference/<name>.py``) starts from the same
seed, takes the same three batches and keys, and the program is held to
it by three numbers, each the worst over its kind (see ``compare``).
A fourth holds the arithmetic of the program's crossbar read, the entry
the step calls for every matrix, to float32: on the step's initial
state and first batch, at the step's token count, the share of the
first layer's read outputs that lie half an ADC step or more from the
reference's (see ``read_share``).  One ADC code that rounds the other
way changes every later code of the step, so no output of a whole step
tells float32 reads from bfloat16 ones; the reads themselves do.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time

import numpy as np

import bench


def _batches(vocab: int, b: int, s: int, n: int, seed: int) -> list:
    """``n`` distinct batches of uniform random tokens (next-token
    labels), made on the host from the seed."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = rng.integers(0, vocab, (b, s + 1), dtype=np.int32)
        out.append((t[:, :-1], t[:, 1:]))
    return out


def _relative_gaps(prog: dict, ref: dict) -> tuple:
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖), over the
    leaves whose reference norm is at least a thousandth of the median;
    returns (gap, leaf, leaves left out)."""
    med = float(np.median([ref[k] for k in ref]))
    worst, leaf, out = 0.0, "", []
    for k in ref:
        if ref[k] < 1e-3 * med:
            out.append(k)
            continue
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not gap <= worst:           # NaN wins
            worst, leaf = gap, k
    return worst, leaf, out


def program_reader(cfg):
    """``read(x, g, ref, w_scale, transpose)``: the program's crossbar
    read (``repro.core.xbar_ops.vmm`` / ``mvm``, the entry the step's
    layers call; the Pallas kernel on a TPU)."""
    import jax
    from repro.core.tiled_analog import crossbar_from_model
    from repro.core.xbar_ops import mvm, vmm
    xcfg = crossbar_from_model(cfg)
    return jax.jit(lambda x, g, r, w, t: (mvm if t else vmm)(x, g, r, w,
                                                             xcfg),
                   static_argnums=4)


def read_share(ref_mod, params, tokens, model: dict, dev: dict,
               reader) -> float:
    """Share of the first layer's read outputs in which ``reader`` lies
    half an ADC step or more from the float32 reference: each matrix's
    forward read of its drive in the reference's forward pass of
    ``tokens``, and its transpose read driven by that read's output."""
    import jax
    import jax.numpy as jnp
    reads = jax.jit(lambda p, t: ref_mod.first_layer_reads(
        p, t, model, dev))(params, tokens)
    ref_read = jax.jit(lambda x, g, r, w, t: ref_mod.analog_read(
        x, g, r, w, dev, t, "highest", with_lsb=True), static_argnums=4)
    off = total = 0
    for drive, g, ref, w_scale in reads.values():
        for transpose in (False, True):
            want, lsb = ref_read(drive, g, ref, w_scale, transpose)
            got = reader(drive, g, ref, w_scale, transpose)
            off += int(jnp.sum(~(jnp.abs(got - want) < 0.5 * lsb)))
            total += want.size
            drive = want
    return off / total


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """The compared numbers, each beside its limit.

    * ``loss_gap``: the worst of the three steps' |loss - ref| / |ref|;
    * ``first_change_gap``: the worst leaf's gap between the norms of the
      program's and the reference's first change (a gradient times the
      learning rate for a digital leaf, the first write for conductances);
    * ``change3_gap``: the same for the change after three steps;
    * ``read_code_share``: the program's crossbar read against the
      float32 reference (``read_share``).
    A leaf whose reference change is under a thousandth of the median
    leaf's moves by round-off alone and is left out of a change number
    (the primary array of a periodic-carry container does not move in
    its first step, only in a carry sweep).  A number the cell's limits
    do not name is reported on stderr and not compared: no reading
    separates it from the sound runs' (PERF.md).
    """
    first, leaf1, out = _relative_gaps(prog["first"], ref["first"])
    three, leaf3, out3 = _relative_gaps(prog["three"], ref["three"])
    numbers = {
        "loss_gap": max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                        for p, r in zip(prog["losses"], ref["losses"])),
        "first_change_gap": first, "change3_gap": three,
        "read_code_share": prog["read_share"]}
    for when in ("first", "three"):
        print(f"change norms after {when}, leaf: program / reference: "
              + ", ".join(f"{k}: {prog[when][k]!r} / {ref[when][k]!r}"
                          for k in ref[when]), file=sys.stderr)
    print(f"worst leaves: first change {leaf1}, after three {leaf3}; "
          f"left out {out} and {out3}", file=sys.stderr)
    checks, ok = {}, True
    for name, value in numbers.items():
        if name in limits:
            ok &= bench.check(checks, name, value, limits[name])
        else:
            print(f"not compared {name}: {value!r}", file=sys.stderr)
    sys.stderr.flush()
    return ok, checks


def run(cell: dict, args, t_start: float, factory=None,
        reader=None) -> tuple:
    """One run of a ``train`` cell; returns (result, checks).
    ``factory(cfg, lr)`` stands in for the program's step factory, and
    ``reader`` for its crossbar read (``program_reader``), in the
    control and in the tests of planted faults."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.train.analog_lm import init_state, make_analog_sgd_step

    devs, peaks = bench.require_devices(cell["chips"])
    marks = {"chip": time.perf_counter() - t_start}
    bench.set_compile_cache()
    conf, traffic = cell["config"], cell["traffic"]
    # The configuration's dtype states the arithmetic of every matrix
    # product, the digital ones (attention, head) too.
    jax.config.update("jax_default_matmul_precision",
                      "highest" if conf["model"]["dtype"] == "float32"
                      else "default")
    ref_mod = bench.load_module(
        bench.HERE / "reference" / f"{conf['reference']}.py", "reference")
    model = {**conf["model"], **traffic["model"]}
    dev = conf["device"]
    cfg = ModelConfig(**model)
    b, s = conf["train_batch"]
    lr = traffic["lr"]

    key = bench.seed_key(args.seed)
    spec = jax.eval_shape(lambda k: init_state(k, cfg), key)
    state = jax.jit(lambda k: {**ref_mod.make_state(k, spec, dev),
                               "step": jnp.full((), traffic["initial_step"],
                                                jnp.int32)})(key)
    jax.block_until_ready(state)
    marks["state"] = time.perf_counter() - t_start
    step = (factory or make_analog_sgd_step)(cfg, lr=lr)
    norms = jax.jit(lambda k, st: ref_mod.change_norms(k, st, spec, dev))
    host = _batches(cfg.vocab, b, s, traffic["pool"], args.seed)
    feed = [{"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
            for x, y in host]
    step_keys = [jax.random.fold_in(jax.random.fold_in(key, 1), i)
                 for i in range(3)]

    # The first three steps: the window's own call and feed, read back.
    prog = {"losses": []}
    for i in range(3):
        state, mets = step(state, feed[i], step_keys[i])
        prog["losses"].append(float(mets["loss"]))
        marks[f"step{i + 1}"] = time.perf_counter() - t_start
        if i == 0:
            prog["first"] = {k: float(v) for k, v in
                             norms(key, state).items()}
    prog["three"] = {k: float(v) for k, v in norms(key, state).items()}
    wkeys = [jax.random.fold_in(jax.random.fold_in(key, 2), i)
             for i in range(len(feed))]
    jax.block_until_ready(wkeys)
    setup_s = time.perf_counter() - t_start
    print(f"set-up seconds from process start: {marks}, all {setup_s!r}",
          file=sys.stderr, flush=True)

    trace_dir = bench.ROOT / ".bench_trace" / cell["name"]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    steps, losses, window_s = _window(step, state, feed, wkeys, args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    state = None
    info = bench.device_info(devs)
    compiles = step.compiles
    del step
    gc.collect()

    result = {"attempted": steps,
              "failed": sum(not math.isfinite(v) for v in losses),
              "device": info}
    tokens_per_s = steps * b * s / window_s
    run_data = {"steps": steps, "tokens_per_s": tokens_per_s, "peaks": peaks,
                "model": model, "seq": s, "batch": b,
                "pulse_train": model.get("analog_update_mode")
                == "pulse_train"}
    if args.trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, steps)
        run_data["trace"] = red
        result["metrics"] = bench.read_per_layer(cell, run_data)
        info["busy_s"] = red["busy_s"]
        info["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    else:
        values = {"setup_s": setup_s, "train_tokens_per_s": tokens_per_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}

    t_ref = time.perf_counter()
    params = jax.jit(lambda k: ref_mod.make_state(k, spec, dev))(key)
    prog["read_share"] = read_share(ref_mod, params["params"],
                                    jnp.asarray(host[0][0]), model, dev,
                                    reader or program_reader(cfg))
    ref = reference_run(ref_mod, params, dev, model, lr, key, host[:3],
                        step_keys, norms, traffic["initial_step"])
    print(f"reference seconds: {time.perf_counter() - t_ref!r}",
          file=sys.stderr, flush=True)
    ok, checks = compare(prog, ref, cell["limits"])
    ok &= bench.check(checks, "compiles", float(compiles), 1.0)
    result["correct"] = bool(ok and result["failed"] == 0)
    return result, checks


def _window(step, state, feed, keys, seconds: float) -> tuple:
    """Steps for ``seconds``, one in flight; returns (steps, losses,
    seconds from the first dispatch to the last step's end).  The longest
    wait between two steps' ends goes to stderr, to tell a stall from a
    slower step."""
    import jax
    from jax.profiler import TraceAnnotation
    n = len(feed)
    losses, pending = [], None
    i = 3
    t0 = t_end = time.perf_counter()
    longest = 0.0
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.batch"):
                batch, k = feed[i % n], keys[i % n]
            with TraceAnnotation("bench.dispatch"):
                state, mets = step(state, batch, k)
            if pending is not None:
                with TraceAnnotation("bench.wait"):
                    losses.append(float(pending["loss"]))
                longest = max(longest, time.perf_counter() - t_end)
                t_end = time.perf_counter()
            pending = mets
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.wait"):
            losses.append(float(pending["loss"]))
            jax.block_until_ready(state)
    print(f"window: {len(losses)} steps, longest between two step ends "
          f"{longest!r} s", file=sys.stderr, flush=True)
    return len(losses), losses, time.perf_counter() - t0


def reference_run(ref_mod, params, dev, model, lr, key, host, step_keys,
                  norms, initial_step: int) -> dict:
    """The reference's three steps from the initial state ``params``
    (donated), with the same batches and keys."""
    import jax
    import jax.numpy as jnp
    stepper = jax.jit(lambda p, t, l, k, i: ref_mod.sgd_step(
        p, t, l, k, i, model=model, dev=dev, lr=lr), donate_argnums=(0,))
    out = {"losses": []}
    for i, (x, y) in enumerate(host):
        params["params"], loss = stepper(
            params["params"], jnp.asarray(x), jnp.asarray(y), step_keys[i],
            jnp.int32(initial_step + i))
        out["losses"].append(float(loss))
        if i == 0:
            out["first"] = {k: float(v) for k, v in norms(key, params).items()}
    out["three"] = {k: float(v) for k, v in norms(key, params).items()}
    return out
