"""The main-path Pallas kernels, compiled for a DESCRIBED TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (jax.experimental.topologies) — so these tests
catch, on the CPU and at no chip time, what interpret mode cannot: a slice
the tiling refuses, a kernel over the fast-memory limit, a kernel the
partitioner cannot split. Nothing runs; a passing compile is not a chip
run. Shapes are the real widths chip_smoke.py and the llama presets use.

All in ONE file, the topology described inside a module-scoped fixture
(never at import, never autouse): only one process at a time may load the
TPU library, so only the worker that is handed this file does.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import rms_norm as rn
from paddle_tpu.ops.pallas import varlen_attention as va

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)        # chip_smoke.py lives at the root


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(lowered):
    compiled = lowered.compile()      # raises what the chip's compiler would
    return lowered.as_text().count("tpu_custom_call"), compiled


@pytest.mark.parametrize("hidden", [2048, 4096, 5120])
def test_rms_norm_forward_compiles(one_chip, hidden):
    x = jax.ShapeDtypeStruct((16384, hidden), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((hidden,), jnp.float32, sharding=one_chip)
    n, _ = _custom_calls(jax.jit(
        lambda a, b: rn._pallas_forward(a, b, 1e-5)).lower(x, w))
    assert n == 1


@pytest.mark.parametrize("shape,dropout", [
    ((2, 32, 4096, 128), 0.0),      # llama2-7b heads
    ((2, 16, 4096, 128), 0.0),      # ~1B flagship
    ((2, 12, 512, 64), 0.0),        # BERT-base
    ((2, 12, 512, 64), 0.1),        # ... with in-kernel dropout
])
def test_flash_attention_forward_and_backward_compile(one_chip, monkeypatch,
                                                      shape, dropout):
    # the custom VJP dispatches on use_pallas(); conftest defaults it off
    monkeypatch.setenv("PT_USE_PALLAS", "1")
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    block = min(fa.DEFAULT_BLOCK_Q, shape[2])

    def fwd(q_, k_, v_, s_):
        return fa._pallas_forward(q_, k_, v_, None, s_, True, dropout,
                                  block, block)

    n, _ = _custom_calls(jax.jit(fwd).lower(q, q, q, seed))
    assert n == 1

    def loss(q_, k_, v_, s_):
        # through the custom VJP, whose backward is the two Pallas kernels
        return jnp.sum(fa._flash_attention(q_, k_, v_, None, s_, True,
                                           dropout).astype(jnp.float32))

    n, _ = _custom_calls(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                         .lower(q, q, q, seed))
    assert n == 3       # forward, dkv, dq


def test_varlen_attention_forward_and_backward_compile(one_chip):
    q = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)

    def loss(q_, k_, v_, s_):
        return jnp.sum(va._varlen_attention(q_, k_, v_, s_, s_, True)
                       .astype(jnp.float32))

    n, _ = _custom_calls(jax.jit(loss).lower(q, q, q, seg))
    assert n == 1
    n, _ = _custom_calls(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                         .lower(q, q, q, seg))
    assert n == 3


def _kernel_programs(one_chip):
    """{kernel name: (function, abstract arguments)}: each of the eight
    `pl.pallas_call` sites, reached as the program reaches it."""
    q = jax.ShapeDtypeStruct((1, 4, 512, 128), jnp.bfloat16,
                             sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((512,), jnp.float32, sharding=one_chip)

    def flash(q_, k_, v_, s_):
        return jnp.sum(fa._flash_attention(q_, k_, v_, None, s_, True, 0.0)
                       .astype(jnp.float32))

    def varlen(q_, k_, v_, s_):
        return jnp.sum(va._varlen_attention(q_, k_, v_, s_, s_, True)
                       .astype(jnp.float32))

    flash_grad = jax.grad(flash, argnums=(0, 1, 2))
    varlen_grad = jax.grad(varlen, argnums=(0, 1, 2))
    return {
        "flash_attention_fwd": (flash, (q, q, q, seed)),
        "flash_attention_dkv": (flash_grad, (q, q, q, seed)),
        "flash_attention_dq": (flash_grad, (q, q, q, seed)),
        "varlen_attention_fwd": (varlen, (q, q, q, seg)),
        "varlen_attention_dkv": (varlen_grad, (q, q, q, seg)),
        "varlen_attention_dq": (varlen_grad, (q, q, q, seg)),
        "rms_norm": (lambda a, b: rn.rms_norm(a, b, 1e-5), (x, w)),
        "rms_norm_noweight": (lambda a: rn.rms_norm(a, None, 1e-5), (x,)),
    }


@pytest.mark.parametrize("kernel", [
    "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq",
    "varlen_attention_fwd", "varlen_attention_dkv", "varlen_attention_dq",
    "rms_norm", "rms_norm_noweight"])
def test_kernel_names_reach_the_chip_program(one_chip, monkeypatch, kernel):
    """Every `pl.pallas_call` names its kernel: the lowered program's
    `kernel_name`, and in the COMPILED program both the custom call's
    instruction name (what a device trace's event starts with) and its
    `kernel_metadata`, which was `{}` before the kernels had names."""
    import re

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    fn, args = _kernel_programs(one_chip)[kernel]
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    compiled = lowered.compile().as_text()
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'kernel_metadata=\{\s*([^}]*)\}', compiled)
    assert calls and all(meta.strip() for _, meta in calls), calls
    # the instruction is named after the kernel, inside whatever JAX's
    # wrappers add (`transpose_jvp_flash_attention_dq__.1` in a backward)
    assert any(kernel in name
               and f'"kernel":"{kernel}"' in meta.replace(" ", "")
               for name, meta in calls), calls


def abstract_trainer(config, mesh, **kwargs):
    """A HybridTrainer whose parameters and optimizer state are shapes
    with shardings on `mesh` — described devices hold no arrays, so the
    constructor's materializing init cannot run.
    (tools/tpu_compile_smoke.py borrows this for chip_smoke's real sizes.)"""
    import functools

    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.models import llama

    class AbstractTrainer(HybridTrainer):
        def _init_state(self, seed):
            def shapes(dtype=None):
                return jax.tree.map(
                    lambda a, sh: jax.ShapeDtypeStruct(
                        a.shape, dtype or a.dtype, sharding=sh),
                    jax.eval_shape(functools.partial(
                        llama.init_stacked_params, self.config),
                        jax.random.key(seed)),
                    self.param_shardings)

            self.params = shapes()
            self.opt_state = {"m": shapes(jnp.float32),
                              "v": shapes(jnp.float32)}

    return AbstractTrainer(config, mesh, **kwargs)


@pytest.mark.parametrize("pp,sharding,mp", [
    (1, 1, 1), (1, 2, 2), (1, 1, 4),
    (2, 1, 2),      # the compiled pipeline: kernels nested in its 'pp' ring
])
def test_whole_train_step_compiles_with_kernels(topo, monkeypatch,
                                                pp, sharding, mp):
    """The whole HybridTrainer step (small widths that tile) for one chip
    and for four chips, stacked and pipelined: the kernels are in the
    program, and on a multi-device mesh they sit in shard_map — the
    partitioner refuses a bare Mosaic kernel ("cannot be automatically
    partitioned")."""
    import chip_smoke
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import llama

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    config = dataclasses.replace(
        llama.LLAMA_PRESETS["tiny"], num_attention_heads=4,
        num_key_value_heads=4, dtype="bfloat16")      # head_dim 64
    tr = abstract_trainer(
        config, build_mesh(pp=pp, sharding=sharding, mp=mp,
                           devices=topo.devices),
        pipeline_micro_batches=2 if pp > 1 else None)
    lowered = tr.lower((2, 512))
    calls = chip_smoke.kernel_calls_in(lowered.as_text())
    assert calls["flash_attention"] > 0 and calls["rms_norm"] > 0, calls
    assert calls["total"] == calls["flash_attention"] + calls["rms_norm"]
    compiled = lowered.compile()
    assert chip_smoke.predicted_bytes(compiled) > 0


def test_bare_kernel_on_a_mesh_is_refused(topo):
    """What per_shard exists for: the same kernel, jitted over four
    devices without shard_map, does not lower."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("a", "b"))
    x = jax.ShapeDtypeStruct((1024, 256), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("a", None)))
    w = jax.ShapeDtypeStruct((256,), jnp.float32,
                             sharding=NamedSharding(mesh, P(None)))
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(lambda a, b: rn._pallas_forward(a, b, 1e-5)).lower(x, w)
