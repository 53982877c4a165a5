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
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import kv_page_write as kw
from paddle_tpu.ops.pallas import paged_attention as pa
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


@pytest.mark.parametrize("name,t,hq,hkv,d,bs,rows,max_blocks,dtype", [
    # Mistral-7B serve cell: GQA 32/8, 32-token pages, max_seq 1280
    ("mistral7b_mixed_step", 256, 32, 8, 128, 32, 33, 40, jnp.bfloat16),
    # chip_smoke's engine: llama2-7b MHA, token budget 512
    ("llama2_7b_mha", 512, 32, 32, 128, 32, 9, 18, jnp.bfloat16),
    # a decode window's step: as many tokens as rows
    ("decode_window_rows4", 4, 32, 8, 128, 32, 33, 40, jnp.bfloat16),
    ("float32_block16_head256", 128, 8, 2, 256, 16, 5, 8, jnp.float32),
    # the hybrid cell's one attention layer: 16 query heads a KV head
    ("nemotron3s_gqa_32_2", 512, 32, 2, 128, 32, 129, 96, jnp.bfloat16),
])
def test_paged_attention_compiles(one_chip, name, t, hq, hkv, d, bs, rows,
                                  max_blocks, dtype):
    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = s((2, 1 + rows * max_blocks // 2, hkv, bs, d), dtype)
    n, _ = _custom_calls(jax.jit(
        lambda *a: pa.paged_attention(*a, layer_idx=1)).lower(
            s((t, hq, d), dtype), s((t, hkv, d), dtype),
            s((t, hkv, d), dtype), cache, cache, s((rows, max_blocks)),
            s((rows,)), s((rows + 1,))))
    assert n == 1


def test_ssm_state_update_compiles_in_place(one_chip, monkeypatch):
    """The state-update kernel at the hybrid cell's sizes (5 layers, 129
    slots, state [128, 64, 128] float32): one custom call, the 2.7e9 B
    stack aliased in and out, no temporary of its size."""
    from paddle_tpu.ops.pallas import ssm_state_update as ssu

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    L, S, H, P, N, G, R = 5, 129, 128, 64, 128, 8, 129

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f32, bf16 = jnp.float32, jnp.bfloat16
    lowered = jax.jit(
        lambda *a: ssu.ssm_state_update(*a, layer_idx=3),
        donate_argnums=(0,)).lower(
            s((L, S, H, P, N), f32), s((R, H, P), bf16), s((R, H), f32),
            s((H,), f32), s((R, G, N), bf16), s((R, G, N), bf16),
            s((H,), f32), s((R,)), s((R,)), s((R,)))
    n, compiled = _custom_calls(lowered)
    assert n == 1
    m = compiled.memory_analysis()
    stack = L * S * H * P * N * 4
    assert m.alias_size_in_bytes >= stack
    assert m.temp_size_in_bytes < stack // 100


def test_hybrid_step_holds_its_kernels_and_copies_no_state_stack(
        one_chip, monkeypatch):
    """The hybrid model's mixed step at chip_smoke's sizes, compiled for
    the described chip: one state-update kernel a state-space layer, one
    paged-attention kernel and one page-write kernel, XLA's grouped product
    for the expert layers, and no copy of the float32 state stack or of the
    page stack (both donated and updated where they lie)."""
    import chip_smoke
    from paddle_tpu.inference.serving import PagedServingConfig
    from paddle_tpu.models.nemotron_h import NemotronH, NemotronHSpec

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    cfg = chip_smoke.chip_config()
    sizes = dict(cfg.hybrid)
    spec = NemotronHSpec.from_config(sizes, held=sizes.pop("held"))
    scfg = PagedServingConfig(**cfg.hybrid_serving)

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    model = NemotronH.__new__(NemotronH)
    model.spec = spec
    model.params = {k: s(*v) for k, v in spec.param_shapes().items()}
    st = model.layer_states()
    b1, t = scfg.max_batch + 1, scfg.token_budget
    cache = s((st.attention_layers, scfg.num_blocks, st.kv_heads,
               scfg.block_size, st.head_dim), jnp.bfloat16)
    rows = [s((r.layers, b1) + r.shape, r.dtype) for r in st.row_states]
    lowered = jax.jit(model.serving_step,
                      donate_argnums=(7, 8, 9, 10)).lower(
        model.params, s((t,)), s((b1,)), s((b1,)), s((b1,)), s((b1 + 1,)),
        s((b1, scfg.max_blocks_per_seq)), cache, cache, *rows, s((b1,)))
    hlo = lowered.compile().as_text()
    calls = chip_smoke.kernel_calls_in(hlo)
    assert calls["ssm_state_update"] == spec.count("M") == 5
    assert calls["paged_attention"] == spec.count("*") == 1
    assert calls["kv_page_write"] == 1       # its pages: written in place
    assert hlo.count("ragged-dot") >= 2 * spec.count("E")
    # the row state and the pages: in the text, and never copied whole
    assert chip_smoke.whole_array_copies_in(hlo, rows[0]) == 0
    assert chip_smoke.whole_array_copies_in(hlo, cache) == 0


def test_ssm_state_update_compiles_at_groups_equal_heads(one_chip,
                                                          monkeypatch):
    """The state-update kernel at lightning attention's sizes (6 layers, 25
    slots, 32 heads of [128, 128] float32, every head its own B and C):
    blocks of 16 heads, `dt x` and `y` as `[R, 2, 128, 16]`, the stack
    aliased in and out."""
    from paddle_tpu.ops.pallas import ssm_state_update as ssu

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    L, S, H, P, N, R = 6, 25, 32, 128, 128, 25

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f32, bf16 = jnp.float32, jnp.bfloat16
    lowered = jax.jit(
        lambda *a: ssu.ssm_state_update(*a, layer_idx=3),
        donate_argnums=(0,)).lower(
            s((L, S, H, P, N), f32), s((R, H, P), bf16), s((R, H), f32),
            s((H,), f32), s((R, H, N), bf16), s((R, H, N), bf16),
            s((H,), f32), s((R,)), s((R,)), s((R,)))
    n, compiled = _custom_calls(lowered)
    assert n == 1
    assert f"f32[{R},{H // 16},{P},16]" in compiled.as_text()
    m = compiled.memory_analysis()
    stack = L * S * H * P * N * 4
    assert m.alias_size_in_bytes >= stack
    assert m.temp_size_in_bytes < stack // 20


def test_sparse_step_holds_its_kernels_and_copies_no_stack(one_chip,
                                                           monkeypatch):
    """MiniCPM-SALA's mixed step at the cell's widths and serving sizes
    (one block-sparse and one lightning layer of the eight), compiled for
    the described chip: the walk over selected pages, the page write and
    the state update as kernels, and no copy of the page stacks, of the
    compressed-key cache beside them or of the lightning state."""
    import chip_smoke
    from paddle_tpu.models.minicpm_sala import MiniCPMSala, MiniCPMSalaSpec

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    spec = MiniCPMSalaSpec(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        mixer_types=("minicpm4", "lightning-attn"), num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, lightning_nh=32,
        lightning_nkv=32, lightning_head_dim=128, rms_norm_eps=1e-6,
        rope_theta=10000, scale_emb=12, scale_depth=1.4, dim_model_base=256,
        published_layers=32)

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    model = MiniCPMSala.__new__(MiniCPMSala)
    model.spec = spec
    model.params = {k: s(*v) for k, v in spec.param_shapes().items()}
    st = model.layer_states()
    b1, t, mb, nb = 25, 512, 652, 24 * 652 + 1
    cache = s((st.attention_layers, nb, st.kv_heads, 64, st.head_dim),
              jnp.bfloat16)
    kept = [s((r.layers, b1) + r.shape, r.dtype) for r in st.row_states] \
        + [s((p.layers, nb) + p.shape, p.dtype) for p in st.page_sides]
    lowered = jax.jit(model.serving_step,
                      donate_argnums=tuple(range(7, 9 + len(kept)))).lower(
        model.params, s((t,)), s((b1,)), s((b1,)), s((b1,)), s((b1 + 1,)),
        s((b1, mb)), cache, cache, *kept, s((b1,)))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # ONE call a block-sparse layer, list walk and wide walk inside it, with
    # the operands the roofline's work file tells the kernel by
    (call,) = [ln.strip() for ln in hlo.splitlines()
               if ln.lstrip().startswith("%sparse_paged_attention")
               and " custom-call(" in ln]
    from benchmark.work import sparse_paged_attention as work_file

    flops, nbytes = work_file.work(_with_operand_shapes(call), {}, rows=1)
    assert flops == 4 * 128 * 64 * 16 and nbytes == 2 * 64 * 128 * 2
    calls = chip_smoke.kernel_calls_in(hlo)
    assert calls["ssm_state_update"] == calls["kv_page_write"] == 1
    for stack in [cache] + kept:
        assert chip_smoke.whole_array_copies_in(hlo, stack) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def _with_operand_shapes(call):
    """A compiled custom call's text as a trace's event names it: each
    operand with its shape (the compiled text lists the shapes under
    `operand_layout_constraints`, in the operands' order)."""
    import re

    head, rest = call.split(" custom-call(", 1)
    names, tail = rest.split("), custom_call_target=", 1)
    shapes = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", tail.split(
        "operand_layout_constraints={", 1)[1].split("}}", 1)[0] + "}")
    names = [n.split("*/")[-1] for n in names.split(", ")]
    assert len(shapes) == len(names)
    return (f"{head} custom-call("
            + ", ".join(f"{s} {n}" for s, n in zip(shapes, names))
            + "), custom_call_target=" + tail)


PAGED_ENGINE = dict(vocab_size=512, hidden_size=512, num_layers=2,
                    num_heads=4, num_kv_heads=2, ffn_size=1024,
                    block_size=32, num_blocks=65, max_batch=8,
                    max_blocks_per_seq=8, token_budget=128,
                    dtype="bfloat16")


def _engine_programs(one_chip, kernels, monkeypatch):
    """{program: lowered} of a small bf16 engine whose heads and pages
    tile (head_dim 128, 32-token pages): the mixed step, a verify step and
    a decode window, lowered for the described chip. A model each time:
    its step programs are traced once, with or without the kernels."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    import chip_smoke

    monkeypatch.setenv("PT_USE_PALLAS", "1" if kernels else "0")
    scfg = PagedServingConfig(**PAGED_ENGINE)
    paddle.seed(0)
    model = PagedCausalLM(scfg)
    model.eval()
    eng = ServingEngine.from_model(model, scfg, seed=0)

    def shp(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    return scfg, {
        "mixed_step": eng._compiled.lower(
            *chip_smoke.abstract_step_args(eng, scfg, shp)),
        "verify_step": eng._compiled_verify.lower(
            *chip_smoke.abstract_step_args(eng, scfg, shp, tokens=16)),
        "decode_window": eng._decode_window_fn(4, 8, "greedy").lower(
            *chip_smoke.abstract_window_args(eng, scfg, 4, 8, shp)),
    }


def test_engine_steps_hold_the_paged_kernel_and_no_gathered_view(
        one_chip, monkeypatch, capsys):
    """The mixed step, the verify step and the decode window each hold one
    `paged_attention` custom call a layer, and no buffer of the gathered
    formulation (`[T, HKV, max_seq, D]`, a token's copy of its row, and
    `[B, HKV, max_seq, D]`, the dense view) is left in the compiled text;
    the same programs with kernels off hold both, so the search bites.
    Prints `memory_analysis()` of each, before (reference) and after."""
    import re

    import chip_smoke

    def views(scfg, text, t):
        hkv, d = scfg.num_kv_heads, scfg.head_dim
        return [len(re.findall(rf"bf16\[{n},{hkv},{scfg.max_seq},{d}\]", text))
                for n in (t, scfg.max_batch + 1)]

    tokens = {"mixed_step": PAGED_ENGINE["token_budget"],
              "verify_step": 16, "decode_window": 4}
    report = []
    for kernels in (False, True):
        scfg, programs = _engine_programs(one_chip, kernels, monkeypatch)
        for name, lowered in programs.items():
            compiled = lowered.compile()
            # the layers share one lowering of the kernel: count where
            # every call stands, in the compiled text
            calls = chip_smoke.kernel_calls_in(compiled.as_text())
            per_token, dense = views(scfg, compiled.as_text(), tokens[name])
            m = compiled.memory_analysis()
            report.append((name, kernels, calls["paged_attention"],
                           m.temp_size_in_bytes,
                           chip_smoke.predicted_bytes(compiled)))
            if kernels:
                assert calls["paged_attention"] == scfg.num_layers, name
                assert per_token == 0 and dense == 0, name
            else:
                assert calls["total"] == 0, name
                assert per_token > 0 and dense > 0, name
    with capsys.disabled():
        for name, kernels, n, temp, total in report:
            print(f"\n{name} kernels={kernels}: paged_attention x{n}, "
                  f"temporaries {temp} B, on the device {total} B", end="")
    before = {name: temp for name, k, _, temp, _ in report if not k}
    after = {name: temp for name, k, _, temp, _ in report if k}
    assert after["mixed_step"] < before["mixed_step"]


# the page stacks of the benchmark's two serve cells: (layers, pages, KV
# heads, rows, pages a row, token budget); block 32, head 128, bf16
PAGE_STACKS = {
    "mistral7b-serve-chat": (16, 1025, 8, 32, 40, 256),
    "nemotron3s-serve-chat": (1, 12289, 2, 128, 96, 512),
}


@pytest.mark.parametrize("cell", sorted(PAGE_STACKS))
def test_engine_steps_write_pages_in_place_and_copy_no_stack(
        one_chip, monkeypatch, capsys, cell):
    """`serving_step`, `serving_fresh_prefill`, `serving_spec_verify` and
    the decode window over a page stack of the cell's shape (a narrow model
    under it: the stack's shape is what XLA lays out), compiled for the
    described chip with the stacks donated as the engine donates them: one
    `kv_page_write` call a layer, both stacks aliased, and no `copy` whose
    result has the stack's shape (the scatter's four were half of the chat
    cell's step). The same programs with kernels off hold the scatter and
    such copies, so the search bites. Prints `memory_analysis()` of each,
    before (scatter) and after."""
    import chip_smoke
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    layers, pages, hkv, rows, max_blocks, budget = PAGE_STACKS[cell]
    stack = jax.ShapeDtypeStruct((layers, pages, hkv, 32, 128),
                                 jnp.bfloat16, sharding=one_chip)
    shape = "bf16[%d,%d,%d,32,128]" % stack.shape[:3]
    scfg = PagedServingConfig(
        vocab_size=512, hidden_size=hkv * 128, num_layers=layers,
        num_heads=hkv, num_kv_heads=hkv, ffn_size=512, block_size=32,
        num_blocks=9, max_batch=rows, max_blocks_per_seq=max_blocks,
        token_budget=budget, dtype="bfloat16")

    def shp(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def over_the_stack(args):
        return (*args[:8], stack, stack, *args[10:])

    report = {}
    for kernels in (False, True):
        monkeypatch.setenv("PT_USE_PALLAS", "1" if kernels else "0")
        paddle.seed(0)
        model = PagedCausalLM(scfg)       # a model each: traced once
        model.eval()
        eng = ServingEngine.from_model(model, scfg, seed=0)
        step = chip_smoke.abstract_step_args(eng, scfg, shp)
        programs = {
            "serving_step": (eng._compiled, step),
            "serving_fresh_prefill": (eng._compiled_fresh, step),
            "serving_spec_verify": (
                eng._compiled_verify,
                chip_smoke.abstract_step_args(eng, scfg, shp, tokens=16)),
            "decode_window": (
                eng._decode_window_fn(4, 8, "greedy"),
                chip_smoke.abstract_window_args(eng, scfg, 4, 8, shp)),
        }
        if not kernels:                   # one program shows the "before"
            programs = {"serving_step": programs["serving_step"]}
        for name, (fn, args) in programs.items():
            compiled = fn.lower(*over_the_stack(args)).compile()
            text = compiled.as_text()
            copies = chip_smoke.whole_array_copies_in(text, stack)
            calls = chip_smoke.kernel_calls_in(text)
            m = compiled.memory_analysis()
            report[name, kernels] = (copies, calls["kv_page_write"],
                                     m.temp_size_in_bytes,
                                     m.alias_size_in_bytes)
            if kernels:
                assert copies == 0, (name, copies)
                assert calls["kv_page_write"] == layers, (name, calls)
                assert (calls["paged_attention"] == layers) \
                    == (name != "serving_fresh_prefill"), (name, calls)
                # both stacks are the program's to write: aliased whole
                assert m.alias_size_in_bytes >= 2 * math.prod(stack.shape) * 2
            else:
                assert calls["total"] == 0
                assert copies >= 2, (name, copies)
    with capsys.disabled():
        for (name, kernels), (copies, n, temp, alias) in report.items():
            print(f"\n{cell} {name} kernels={kernels}: {copies} copies of "
                  f"{shape}, kv_page_write x{n}, temporaries {temp} B, "
                  f"aliased {alias} B", end="")
    assert report["serving_step", True][2] < report["serving_step", False][2]


def _kernel_programs(one_chip):
    """{kernel name: (function, abstract arguments)}: each of the ten
    `pl.pallas_call` sites, reached as the program reaches it."""
    q = jax.ShapeDtypeStruct((1, 4, 512, 128), jnp.bfloat16,
                             sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((512,), jnp.float32, sharding=one_chip)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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
        "paged_attention": (
            lambda *a: pa.paged_attention(*a, layer_idx=0),
            (spec((64, 4, 128)), spec((64, 2, 128)), spec((64, 2, 128)),
             spec((1, 9, 2, 32, 128)), spec((1, 9, 2, 32, 128)),
             spec((3, 4), jnp.int32), spec((3,), jnp.int32),
             spec((4,), jnp.int32))),
        "kv_page_write": (
            lambda *a: kw.kv_page_write(*a, layer_idx=0,
                                        last_row_is_padding=True),
            (spec((1, 9, 2, 32, 128)), spec((1, 9, 2, 32, 128)),
             spec((64, 2, 128)), spec((64, 2, 128)),
             spec((3, 4), jnp.int32), spec((3,), jnp.int32),
             spec((4,), jnp.int32))),
    }


@pytest.mark.parametrize("kernel", [
    "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq",
    "varlen_attention_fwd", "varlen_attention_dkv", "varlen_attention_dq",
    "rms_norm", "rms_norm_noweight", "paged_attention", "kv_page_write"])
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
