"""Compile chip_smoke.py's programs for a DESCRIBED TPU v5e — no chip.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (`jax.experimental.topologies`). This script
hands the trainer and the engine the described devices and abstract
shapes, compiles the whole train step (one chip, and the four-chip
layouts), the engine's prefill, mixed (with the paged-attention kernel,
and with the gathered reference it replaced) and decode-window steps at
chip_smoke's real sizes, and the hybrid model's mixed step at its
benchmark cell's sizes, and prints what each needs on a device
(`memory_analysis()`), which kernels it holds and which collectives the
compiler put in. Nothing runs: it says nothing about results or times.
It is how chip_smoke.chip_config's depth and batch were settled (edit
that function to try another size), and what to re-run before a chip call
after a change to those programs:

    python tools/tpu_compile_smoke.py

`jax.default_backend()` is the CPU here, so the kernels are switched on
through PT_USE_PALLAS=1, which `main` sets for this process.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from paddle_tpu.distributed.topology import build_mesh  # noqa: E402
from test_tpu_compile import abstract_trainer  # noqa: E402


def report(name, lowered, t0, stack=None):
    """`stack`: the page (or state) stack the program was given donated;
    its whole-array copies are counted (there must be none)."""
    text = lowered.as_text()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    hlo = compiled.as_text()
    collectives = {k: len(re.findall(rf"\b{k}(-start)?\(", hlo))
                   for k in ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute")}
    out = {"program": name,
           "per_device_bytes": chip_smoke.predicted_bytes(compiled),
           "argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes,
           "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes,
           "kernel_calls": chip_smoke.kernel_calls_in(text),
           "compiled_kernel_calls": chip_smoke.kernel_calls_in(hlo),
           "collectives": {k: v for k, v in collectives.items() if v},
           "compile_s": round(time.perf_counter() - t0, 1)}
    if stack is not None:
        out["stack"] = [str(stack.dtype), *stack.shape]
        out["copies_of_it"] = chip_smoke.whole_array_copies_in(hlo, stack)
    print(json.dumps(out), flush=True)


def compile_train(model, cfg, devices, layout, options):
    t0 = time.perf_counter()
    tr = abstract_trainer(model, build_mesh(devices=devices, **layout),
                          **options)
    name = (f"train L{model.num_hidden_layers} b{cfg.batch} s{cfg.seq} "
            f"{layout or 'one chip'}")
    report(name, tr.lower((cfg.batch, cfg.seq)), t0)


def compile_serve(cfg, device):
    """The engine's three step programs at the smoke's serving config: the
    fresh-prefill step (varlen flash kernel), the mixed prefill/decode
    step (the paged-attention and page-write kernels; before it, the same
    step with the gathered reference and the scatter in the kernels'
    place, for `memory_analysis()` and the copies of the page stack before
    and after) and one decode window. Every program takes the page stacks
    donated, as the engine hands them over."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine, _next_pow2)
    from paddle_tpu.ops.pallas import paged_attention

    scfg = PagedServingConfig(**cfg.serving)
    one = SingleDeviceSharding(device)

    def engine():
        # a model each: its step programs are traced once
        paddle.seed(cfg.seed)
        model = PagedCausalLM(scfg)
        model.eval()
        return ServingEngine.from_model(model, scfg, seed=cfg.seed)

    def shp(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    eng = engine()
    use_kernel = paged_attention.use_kernel    # one question, both kernels
    paged_attention.use_kernel = lambda *a, **k: False
    try:
        t0 = time.perf_counter()
        report(f"serve mixed step L{scfg.num_layers} "
               f"T{scfg.token_budget}, gathered reference",
               eng._compiled.lower(
                   *chip_smoke.abstract_step_args(eng, scfg, shp)), t0,
               stack=eng._kc)
    finally:
        paged_attention.use_kernel = use_kernel
    eng = engine()
    T = scfg.token_budget
    step_args = chip_smoke.abstract_step_args(eng, scfg, shp)
    t0 = time.perf_counter()
    report(f"serve fresh-prefill L{scfg.num_layers} T{T}",
           eng._compiled_fresh.lower(*step_args), t0, stack=eng._kc)
    t0 = time.perf_counter()
    report(f"serve mixed step L{scfg.num_layers} T{T}",
           eng._compiled.lower(*step_args), t0, stack=eng._kc)
    rows = min(_next_pow2(len(cfg.prompt_lens)), scfg.max_batch)
    n = 8
    t0 = time.perf_counter()
    window = eng._decode_window_fn(rows, n, "greedy")
    report(f"serve decode window L{scfg.num_layers} rows{rows} n{n}",
           window.lower(*chip_smoke.abstract_window_args(
               eng, scfg, rows, n, shp)), t0, stack=eng._kc)


def compile_serve_hybrid(device, config_file=os.path.join(
        REPO, "benchmark", "configs", "nemotron3-super-serve-ep8.json")):
    """The hybrid model's mixed step at the benchmark cell's real sizes
    (models/nemotron_h.py: 5 state-space layers over 129 row slots, one
    paged-attention layer, 64 of 512 experts held), from abstract
    parameters: what it needs on a device, its kernels, and that no
    operation COPIES the float32 state stack (it is donated and updated
    where it lies: a `copy` of `f32[5,129,128,64,128]` is 2.7e9 B a step)
    or the page stack (`bf16[1,12289,2,32,128]`, written where it lies by
    `kv_page_write`; the scatter cost four copies of it a step)."""
    from paddle_tpu.models.nemotron_h import NemotronH, NemotronHSpec

    with open(config_file) as f:
        config = json.load(f)
    held, s = config["experts_held"], config["serving"]
    spec = NemotronHSpec.from_config(
        config, held=(held["first"], held["count"]),
        n_routed_experts=held["of"], dtype=s["dtype"])
    one = SingleDeviceSharding(device)

    def shp(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)

    model = NemotronH.__new__(NemotronH)
    model.spec = spec
    model.params = {k: shp(*v) for k, v in spec.param_shapes().items()}
    st = model.layer_states()
    b1, t = s["max_batch"] + 1, s["token_budget"]
    cache = shp((st.attention_layers, s["num_blocks"], st.kv_heads,
                 s["block_size"], st.head_dim), s["dtype"])
    rows = [shp((r.layers, b1) + r.shape, r.dtype) for r in st.row_states]
    t0 = time.perf_counter()
    lowered = jax.jit(model.serving_step,
                      donate_argnums=(7, 8, 9, 10)).lower(
        model.params, shp((t,)), shp((b1,)), shp((b1,)), shp((b1,)),
        shp((b1 + 1,)), shp((b1, s["max_blocks_per_seq"])), cache, cache,
        *rows, shp((b1,)))
    report(f"serve hybrid mixed step {spec.hybrid_override_pattern} T{t} "
           f"rows{b1}", lowered, t0, stack=cache)
    print(json.dumps({
        "state_stack": [str(rows[0].dtype), *rows[0].shape],
        "copies_of_it": chip_smoke.whole_array_copies_in(
            lowered.compile().as_text(), rows[0])}), flush=True)


def main():
    # before the first backend or compiler start-up in this process
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["PT_USE_PALLAS"] = "1"
    jax.config.update("jax_platforms", "cpu")
    from jax.experimental import topologies

    cfg = chip_smoke.chip_config()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    compile_train(cfg.llama, cfg, topo.devices[:1], {}, {})
    even = chip_smoke.even_depth(cfg.llama)     # the four-chip phase's
    compile_train(even, cfg, topo.devices[:1], {}, {})
    for layout, options in chip_smoke.LAYOUTS:
        compile_train(even, cfg, topo.devices, layout, options)
    compile_serve(cfg, topo.devices[0])
    compile_serve_hybrid(topo.devices[0])


if __name__ == "__main__":
    main()
