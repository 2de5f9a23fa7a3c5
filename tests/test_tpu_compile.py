"""The main path's Pallas kernels compile for a TPU v5e (Mosaic).

Interpret mode never lowers a kernel through Mosaic, so it cannot see
what the TPU compiler refuses: unaligned blocks, scoped-VMEM overruns,
casts it has no lowering for, bodies too large to compile in time.  These
tests compile each kernel of the analog training step — and the whole
step — for a *described* v5e (no chip attached) at lm100m's published
widths on the paper's 1024x1024 tiles, with the token batch of
``chip_smoke.py`` (8 x 256).  Nothing runs; a compile that passes is not
a chip run.  The compiled step also carries the names the chip
benchmark splits its profiler trace by: each kernel's ``name`` and each
layer's ``jax.named_scope``.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and a
module that loads it while being collected would give each test worker a
different set of tests.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.tiled_analog import crossbar_from_model
from repro.kernels.xbar_update import xbar_outer_update_inline
from repro.kernels.xbar_vmm import (fakequant_read_pallas,
                                    xbar_fused_read_inline)
from repro.train.analog_lm import init_state, make_analog_sgd_step

TOKENS = 8 * 256
CFG = get_config("lm100m").replace(dtype="float32", analog=True,
                                   analog_mode="device")
XCFG = crossbar_from_model(CFG)          # 1024x1024 tiles, taox, 8/8 bits
L, K, N = CFG.n_layers, CFG.d_model, 3 * CFG.d_model   # the wqkv stack
KERNEL = 'custom_call_target="tpu_custom_call"'
# The kernels' names and the layer scopes of the train step, as
# benchmarks/chip/scopes.py reads them from a device trace.
KERNEL_NAMES = ("xbar_vmm", "xbar_update")
SCOPES = ("xbar.read", "xbar.write", "xbar.carry", "attention",
          "head_loss", "layer_scan", "layer")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile can be written to the persistent cache
    # but never read back without a chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("transpose,in_bits", [
    pytest.param(False, 8, id="vmm"), pytest.param(True, 8, id="mvm"),
    pytest.param(False, 10, id="vmm-in_bits10"),
    pytest.param(True, 10, id="mvm-in_bits10")])
def test_fused_read_compiles(one_chip, transpose, in_bits):
    """Both contractions of the read: three bfloat16 passes for the 8-bit
    DAC's codes, the float32 HIGHEST dot for a 10-bit DAC's.  A float32
    model sets JAX's default matmul precision to "highest", which the
    bfloat16 dots must not take up (Mosaic refuses it)."""
    s = lambda *shape: _spec(one_chip, shape)
    drive = N if transpose else K
    xcfg = dataclasses.replace(
        XCFG, adc=dataclasses.replace(XCFG.adc, in_bits=in_bits))
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            c = _compile(lambda x, g, r, w: xbar_fused_read_inline(
                x, g, r, w, xcfg, transpose=transpose, impl="pallas"),
                s(L, TOKENS, drive), s(L, K, N), s(L, K, N), s(L))
        assert KERNEL in c.as_text()


@pytest.mark.parametrize("noise_mode", ["kernel", "none"])
@pytest.mark.parametrize("update_mode", ["outer", "pulse_train"])
def test_update_compiles(one_chip, update_mode, noise_mode):
    """The kernel-noise cases generate the epilogue's normals in-kernel
    (counter PRNG + Box–Muller) over a whole 1024x1024 tile."""
    s = lambda *shape: _spec(one_chip, shape)
    c = _compile(lambda g, x, d, m, seed: xbar_outer_update_inline(
        g, x, d, m, XCFG, seed=seed if noise_mode == "kernel" else None,
        noise_mode=noise_mode, impl="pallas", update_mode=update_mode),
        s(L, K, N), s(L, TOKENS, K), s(L, TOKENS, N), s(L),
        _spec(one_chip, (), jnp.uint32))
    assert KERNEL in c.as_text()


def test_fakequant_read_compiles(one_chip):
    s = lambda *shape: _spec(one_chip, shape)
    c = _compile(lambda x, w: fakequant_read_pallas(x, w, XCFG.adc,
                                                    rows=XCFG.rows),
                 s(TOKENS, K), s(K, N))
    assert KERNEL in c.as_text()


def test_fused_read_refuses_a_block_beyond_vmem(one_chip):
    """The dynamic ADC range is calibrated over one token block, so the
    read holds the whole batch in VMEM; past the cap it says so instead
    of failing inside the compiler."""
    s = lambda *shape: _spec(one_chip, shape)
    with pytest.raises(ValueError, match="VMEM"):
        _compile(lambda x, g, r, w: xbar_fused_read_inline(
            x, g, r, w, XCFG, impl="pallas"),
            s(4 * TOKENS, K), s(K, N), s(K, N), s())


@pytest.fixture(scope="module")
def train_step(one_chip):
    """The whole full-width analog step compiled for one chip, once for
    every test that reads it."""
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(
                             jax.random.PRNGKey(0), CFG)))
    tok = _spec(one_chip, (8, 256), jnp.int32)
    key = _spec(one_chip, (2,), jnp.uint32)
    step = make_analog_sgd_step(CFG, lr=0.05, impl="pallas",
                                read_impl="pallas")
    return step._step.lower(state, {"tokens": tok, "labels": tok},
                            key).compile()


def test_train_step_compiles_with_kernels(train_step):
    """The whole full-width analog step on one chip: every container's
    read and write is a Mosaic kernel, and the program fits the v5e's
    16 GB of HBM."""
    c = train_step
    # At least a VMM, an MVM and a write for each of the 4 containers.
    assert c.as_text().count(KERNEL) >= 12
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9


def _stack_names(names) -> set:
    """Every name on the given name stacks, transforms unwrapped."""
    return {t for n in names for t in re.split(r"[/()]", n) if t}


def test_train_step_names_its_layers(train_step):
    """The compiled step's metadata names both kernels and every layer
    scope the plain step runs (``xbar.carry`` only runs with periodic
    carry; the lowering test below holds it)."""
    text = train_step.as_text()
    ops = re.findall(r'op_name="([^"]*)"', text)
    for k in KERNEL_NAMES:
        assert any(f"/{k}/pallas_call" in op for op in ops), k
    assert set(SCOPES) - {"xbar.carry"} <= _stack_names(ops)
    for op in ops:
        if "pallas_call" in op:
            assert "xbar.read/xbar_vmm/" in op or \
                "xbar.write/xbar_update/" in op, op


def test_lowered_step_names_its_layers():
    """On the CPU: the test-size step with periodic carry, kernels in
    interpret mode, lowered (not compiled) carries every kernel name and
    scope in its debug information."""
    cfg = get_config("lm100m", smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_carry=True, carry_period=4)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    step = make_analog_sgd_step(cfg, lr=0.05, impl="interpret",
                                read_impl="interpret")
    text = step._step.lower(state, {"tokens": tok, "labels": tok},
                            jax.ShapeDtypeStruct((2,), jnp.uint32)
                            ).as_text(debug_info=True)
    # Name stacks hold a "/"; a bare name is a function's call site.
    stacks = [n for n in re.findall(r'loc\("([^"]*)"', text) if "/" in n]
    for k in KERNEL_NAMES:
        assert any(f"{k}/pallas_call" in n for n in stacks), k
    assert set(SCOPES) <= _stack_names(stacks)


# DeepSeek-V2-Lite on one chip: the dense layer and 4 expert layers at
# published widths, 8 of 64 experts held, an eighth of the vocabulary,
# 2 x 2048 tokens a step (the chip benchmark's deepseek-v2-lite-16b).
DS = get_config("deepseek-v2-lite-16b").replace(
    n_layers=5, n_experts=8, n_routed_experts=64, vocab=12800,
    dtype="float32", analog=True, analog_mode="device")
DS_TOKENS = 2 * 2048
MOE_NAMES = ("moe.route", "moe.dispatch", "moe.experts")


@pytest.mark.parametrize("kernel", ["vmm", "mvm", "write"])
def test_expert_kernels_compile_with_row_counts(one_chip, kernel):
    """The expert-batched read (its row counts an SMEM operand, a dynamic
    trip count over the token strips) and write (the counts a
    scalar-prefetch operand of its index maps) for the 8 held experts'
    [up | gate] grids, over a dropless 4096-row buffer: a read block of
    4096 tokens fits the VMEM cap."""
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    e, k, n = DS.n_experts, DS.d_model, 2 * DS.d_ff_expert
    rows = s(e, dt=jnp.int32)
    if kernel == "write":
        c = _compile(lambda g, x, d, m, seed, r: xbar_outer_update_inline(
            g, x, d, m, XCFG, seed=seed, noise_mode="kernel",
            impl="pallas", rows=r),
            s(e, k, n), s(e, DS_TOKENS, k), s(e, DS_TOKENS, n), s(e),
            s(dt=jnp.uint32), rows)
    else:
        transpose = kernel == "mvm"
        c = _compile(lambda x, g, r, w, cnt: xbar_fused_read_inline(
            x, g, r, w, XCFG, transpose=transpose, impl="pallas",
            rows=cnt),
            s(e, DS_TOKENS, n if transpose else k), s(e, k, n), s(e, k, n),
            s(e), rows)
    assert KERNEL in c.as_text()


def test_deepseek_step_compiles_and_names_the_moe_layer(one_chip):
    """The whole 5-layer step for one chip: MLA, the dense layer's MLP,
    the held experts' dropless dispatch and their counted kernels, with
    the expert layer's names (``moe.route``, ``moe.dispatch``,
    ``moe.experts``) in the compiled program's metadata."""
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(
                             jax.random.PRNGKey(0), DS)))
    tok = _spec(one_chip, (2, 2048), jnp.int32)
    key = _spec(one_chip, (2,), jnp.uint32)
    step = make_analog_sgd_step(DS, lr=0.05, impl="pallas",
                                read_impl="pallas")
    with jax.default_matmul_precision("highest"):
        c = step._step.lower(state, {"tokens": tok, "labels": tok},
                             key).compile()
    text = c.as_text()
    assert text.count(KERNEL) >= 3 * 14
    ops = re.findall(r'op_name="([^"]*)"', text)
    assert set(MOE_NAMES) <= _stack_names(ops)
