"""chip_smoke.py — does the system start on the chip?

Drives the two paths users enter by, once, on an attached TPU, through
the same entry points a user calls, and checks what comes out:

  python chip_smoke.py            one chip, one process:
      train    HybridTrainer.step at llama2-7b widths, sequence 4096
      kernels  the Pallas kernels are in the compiled step, and each
               agrees with its own jnp reference on the chip
      serve    ServingEngine.from_model at the same widths, requests of
               unequal length joining mid-flight, greedy parity with
               forward_dense
      eager    eager ops + one jit.TrainStep on paddle.get_device()
  python chip_smoke.py --chips 4  ONLY the multi-chip phase: the same
      configuration, seed and batch trained on devices[:1] and on all
      four chips in this one process.

Each phase prints one JSON object on a line of its own; the LAST line of
standard output is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
It exits non-zero — and prints no such line — when JAX finds no TPU, when
the chip count is not the one asked for, or when any phase fails. Nothing
is caught and reported as a row. Depth is cut to what one 16 GB chip
holds (widths never are); weights and data come from --seed.

The phases are functions of a SmokeConfig, so the CPU test suite can
rehearse each one at a tiny configuration (tests/test_chip_smoke.py); the
command line offers only the real size, and only on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# bf16 tolerance (the tests' "bf16 matmul tol", tests/test_static_passes.py)
BF16_TOL = 2e-2


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run the trainer and the serving engine once on the "
                    "attached TPU and check the results.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): train, kernels, serve and eager "
                         "phases on one chip. 4: only the one-chip versus "
                         "four-chip training comparison.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    return ap.parse_args(argv)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What the phases run. `chip_config` is the only configuration the
    command line can reach."""
    llama: Any                      # models.llama.LlamaConfig
    seq: int
    batch: int
    steps: int
    serving: Dict[str, Any]         # PagedServingConfig kwargs
    prompt_lens: Tuple[int, ...]    # one request each
    n_late: int                     # of those, added while others decode
    max_new: int
    kernels: bool                   # Pallas kernels expected in programs
    seed: int = 0
    # the hybrid serve phase: models.nemotron_h.NemotronHSpec sizes and
    # PagedServingConfig kwargs (prompts, late admissions and max_new as
    # the serve phase's)
    hybrid: Dict[str, Any] = None
    hybrid_serving: Dict[str, Any] = None


# four-chip phase: (build_mesh factorization of the four devices,
# HybridTrainer options); the last is the compiled pipeline
LAYOUTS = (({"sharding": 2, "mp": 2}, {}),
           ({"mp": 4}, {}),
           ({"pp": 2, "mp": 2}, {"pipeline_micro_batches": 2}))


def even_depth(llama):
    """The four-chip phase's model: the pipeline's two stages must divide
    the depth."""
    return dataclasses.replace(
        llama, num_hidden_layers=llama.num_hidden_layers // 2 * 2)


def chip_config(seed: int = 0) -> SmokeConfig:
    """llama2-7b widths (hidden 4096, intermediate 11008, 32 heads of 128,
    vocabulary 32000, bf16, recompute), sequence 4096. Depth and batch are
    what one 16 GB v5e chip holds, settled from memory_analysis() of the
    sandbox compile (tools/tpu_compile_smoke.py), not by trial: 3 layers
    at batch 2 is 13.59e9 bytes for the train step (4 layers at batch 1
    is 15.17e9 and leaves no margin); the serving model is 4 layers, whose
    float32 master, bf16 engine copy and 3.0e9 of mixed-step temporaries
    come to about 10e9. Batch 2 so that the four-chip sharding=2 layout
    divides it."""
    from paddle_tpu.models import llama

    train = dataclasses.replace(llama.LLAMA_PRESETS["llama2-7b"],
                                num_hidden_layers=3)
    serve_layers, block, max_new = 4, 32, 32
    prompt_lens = (512, 64, 200, 333, 128)
    blocks_per_seq = -(-(max(prompt_lens) + max_new) // block)
    serving = dict(
        vocab_size=train.vocab_size, hidden_size=train.hidden_size,
        num_layers=serve_layers, num_heads=train.num_attention_heads,
        num_kv_heads=train.num_attention_heads,
        ffn_size=train.intermediate_size, block_size=block,
        num_blocks=len(prompt_lens) * blocks_per_seq + 1,
        max_batch=len(prompt_lens) + 3, max_blocks_per_seq=blocks_per_seq,
        token_budget=512, dtype="bfloat16")
    # the hybrid phase: a small model of the nemotron_h family whose state
    # ([32, 64, 128] float32 a row a layer), heads (128) and pages (32)
    # tile the kernels; 0.4e9 B of weights
    hybrid = dict(
        vocab_size=8192, hidden_size=1024,
        hybrid_override_pattern="MEMEMEMEM*E", layer_norm_epsilon=1e-5,
        mamba_num_heads=32, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128,
        num_attention_heads=8, num_key_value_heads=2, head_dim=128,
        n_routed_experts=64, num_experts_per_tok=6, moe_latent_size=256,
        moe_intermediate_size=512, moe_shared_expert_intermediate_size=1024,
        routed_scaling_factor=2.5, norm_topk_prob=True, held=(16, 16),
        dtype="bfloat16")
    hybrid_serving = dict(
        vocab_size=8192, hidden_size=1024, num_layers=11, num_heads=8,
        num_kv_heads=2, block_size=block,
        num_blocks=len(prompt_lens) * blocks_per_seq + 1,
        max_batch=len(prompt_lens) + 3, max_blocks_per_seq=blocks_per_seq,
        token_budget=256, dtype="bfloat16")
    return SmokeConfig(llama=train, seq=4096, batch=2, steps=4,
                       serving=serving, prompt_lens=prompt_lens, n_late=2,
                       max_new=max_new, kernels=True, seed=seed,
                       hybrid=hybrid, hybrid_serving=hybrid_serving)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def emit(phase: str, result: Dict[str, Any]) -> None:
    print(json.dumps({"phase": phase, **result}), flush=True)


def require_tpu(chips: int):
    """The devices, or exit non-zero naming what is missing. JAX may come
    up on the CPU with only a warning when the TPU does not initialise,
    so the platform is asserted, never assumed."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU chip: jax.devices()[0].platform is "
            f"{devices[0].platform!r} ({len(devices)} device(s)); this "
            f"script runs only on an attached TPU")
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs exactly {chips} TPU "
            f"chip(s), JAX found {len(devices)}")
    return devices


def assert_on(tree, devices) -> None:
    """Every array of `tree` lives on exactly `devices`."""
    import jax

    want = set(devices)
    for leaf in jax.tree.leaves(tree):
        got = leaf.devices()
        if got != want:
            raise AssertionError(
                f"array on {sorted(map(str, got))}, expected "
                f"{sorted(map(str, want))}")


KERNEL_FAMILIES = ("flash_attention", "varlen_attention", "rms_norm",
                   "paged_attention", "ssm_state_update",
                   "kv_page_write")


def kernel_calls_in(text: str) -> Dict[str, int]:
    """tpu_custom_calls in a program's text, by kernel family. Kernel names
    are the `name=` of each `pl.pallas_call` (ops/pallas/*):
    `flash_attention_fwd`, `varlen_attention_dq`, `rms_norm_noweight` ...
    In a LOWERED program's text they are the calls' `kernel_name`; a kernel
    inside a jitted function that the program calls sixteen times stands
    there once. In a COMPILED program's text (`HloModule ...`), where every
    call is inlined, they are in the custom calls' instruction names."""
    import re

    if text.startswith("HloModule"):
        names = re.findall(
            r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
            text)
        out = {k: sum(k in n for n in names) for k in KERNEL_FAMILIES}
        out["total"] = len(names)
        return out
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    out = {k: sum(n.startswith(k) for n in names) for k in KERNEL_FAMILIES}
    out["total"] = text.count("tpu_custom_call")
    return out


def whole_array_copies_in(compiled_text: str, array) -> int:
    """`copy` operations of a COMPILED program's text whose result has
    `array`'s shape and dtype (anything with `.shape` and `.dtype`): a
    state or page stack re-laid whole. A stack that its step program was
    given donated, and that only aliasing kernels touch, has none."""
    import re

    import numpy as np

    short = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}[
        np.dtype(array.dtype).name]
    shape = "%s[%s]" % (short, ",".join(map(str, array.shape)))
    if shape not in compiled_text:
        raise AssertionError(f"no {shape} in the program's text")
    return len(re.findall(r"= " + re.escape(shape) + r"\S* copy\(",
                          compiled_text))


def reference_dispatches() -> Dict[str, int]:
    """Kernel -> dispatches that gave way to the jnp reference so far."""
    from paddle_tpu.profiler import metrics

    prefix = "pallas/reference_dispatch/"
    return {name[len(prefix):]: n
            for name, n in metrics.snapshot()["counters"].items()
            if name.startswith(prefix)}


def dispatches_since(before: Dict[str, int]) -> Dict[str, int]:
    return {k: n - before.get(k, 0)
            for k, n in reference_dispatches().items()
            if n != before.get(k, 0)}


def check_no_reference_dispatch(cfg: SmokeConfig, delta: Dict[str, int],
                                path: str) -> None:
    if cfg.kernels and delta:
        raise AssertionError(
            f"on {path} kernel dispatches gave way to the jnp reference: "
            f"{delta}")


def check_kernels(cfg: SmokeConfig, calls: Dict[str, int],
                  ref_delta: Dict[str, int]) -> None:
    """A train step's program holds the flash-attention and rms-norm
    kernels when the configuration expects kernels, and none otherwise."""
    if cfg.kernels:
        missing = [f for f in ("flash_attention", "rms_norm")
                   if calls[f] == 0]
        if missing:
            raise AssertionError(
                f"no tpu_custom_call of {missing} in the program: {calls}")
        check_no_reference_dispatch(cfg, ref_delta, "the train step")
    elif calls["total"]:
        raise AssertionError(f"unexpected tpu_custom_calls: {calls}")


def predicted_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def device_memory(device) -> Dict[str, Any]:
    """High-water marks of the device's allocator over the life of the
    process (so a later phase also shows an earlier phase's peak). On a
    TPU a program's temporaries are not in `peak_bytes_in_use`; they come
    out of the reserved region (`peak_bytes_reserved`). The CPU backend
    reports nothing."""
    stats = device.memory_stats()
    if stats is None:
        if device.platform == "tpu":
            raise AssertionError("TPU device reports no memory_stats()")
        return {"peak_bytes_in_use": None}
    return {k: int(stats[k]) for k in
            ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
            if k in stats}


def make_batch(cfg: SmokeConfig):
    import numpy as np

    rng = np.random.RandomState(cfg.seed)
    ids = rng.randint(0, cfg.llama.vocab_size,
                      (cfg.batch, cfg.seq)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)


def train_steps(trainer, cfg: SmokeConfig):
    """cfg.steps steps on one repeated batch; (losses, seconds per step),
    each step ended by block_until_ready."""
    import numpy as np

    ids, labels = make_batch(cfg)
    losses, secs = [], []
    for _ in range(cfg.steps):
        t0 = time.perf_counter()
        loss = trainer.step(ids, labels)
        loss.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return losses, secs


def check_placement(trainer, devices):
    """Every parameter has a shard on every device, each shard holds the
    share of the bytes its PartitionSpec promises, and the devices hold
    equal totals. Returns each device's share of all parameter bytes."""
    import jax
    import numpy as np

    held = {d: 0 for d in devices}
    total = 0
    leaves = zip(jax.tree.leaves(trainer.params),
                 jax.tree.leaves(trainer.param_shardings))
    for leaf, sharding in leaves:
        shards = leaf.addressable_shards
        if {s.device for s in shards} != set(devices):
            raise AssertionError(
                f"parameter {leaf.shape} has shards on "
                f"{len({s.device for s in shards})} of {len(devices)} "
                f"devices")
        axes = [a for part in sharding.spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        promised = 1.0 / np.prod([trainer.mesh.shape[a] for a in axes])
        for s in shards:
            if abs(s.data.nbytes / leaf.nbytes - promised) > 1e-6:
                raise AssertionError(
                    f"parameter {leaf.shape} {sharding.spec}: a shard "
                    f"holds {s.data.nbytes / leaf.nbytes:.3f} of the "
                    f"bytes, its spec promises {promised:.3f}")
            held[s.device] += s.data.nbytes
        total += leaf.nbytes
    share = [held[d] / total for d in devices]
    if max(share) - min(share) > 0.01:
        raise AssertionError(f"uneven parameter bytes: {share}")
    return [round(x, 4) for x in share]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """HybridTrainer on a one-device mesh: compile (memory predicted from
    the compiled program, kernels counted in its text), then a few steps
    on one repeated batch."""
    import jax

    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.distributed.topology import build_mesh

    dev = devices[:1]
    ref0 = reference_dispatches()
    trainer = HybridTrainer(cfg.llama, build_mesh(devices=dev),
                            seed=cfg.seed)
    assert_on((trainer.params, trainer.opt_state), dev)
    n_params = sum(int(p.size) for p in jax.tree.leaves(trainer.params))

    t0 = time.perf_counter()
    lowered = trainer.lower((cfg.batch, cfg.seq))
    calls = kernel_calls_in(lowered.as_text())
    predicted = predicted_bytes(lowered.compile())
    compile_s = time.perf_counter() - t0
    check_kernels(cfg, calls, dispatches_since(ref0))

    losses, secs = train_steps(trainer, cfg)
    assert_on((trainer.params, trainer.opt_state), dev)
    return {"layers": cfg.llama.num_hidden_layers,
            "hidden": cfg.llama.hidden_size, "seq": cfg.seq,
            "batch": cfg.batch, "n_params": n_params,
            "predicted_bytes": predicted,
            **device_memory(dev[0]),
            "compile_s": round(compile_s, 2),
            "first_step_s": round(secs[0], 3),
            "step_s": round(min(secs[1:]), 4),
            "losses": losses, "kernel_calls": calls,
            "reference_dispatches": sum(dispatches_since(ref0).values())}


def phase_kernels(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """Each main-path kernel against its own jnp reference on this device
    at the train step's shapes: flash attention forward and gradients
    (reference in chunks of heads — it materializes S x S logits) and
    rms_norm forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import rms_norm as rn

    c = cfg.llama
    b, h, s, d = cfg.batch, c.num_attention_heads, cfg.seq, c.head_dim
    dt = jnp.bfloat16 if c.dtype == "bfloat16" else jnp.float32
    tol = BF16_TOL if c.dtype == "bfloat16" else 2e-3
    ref0 = reference_dispatches()
    with jax.default_device(devices[0]):
        kq, kk, kv, kg, kx = jax.random.split(jax.random.key(cfg.seed), 5)
        q, k, v, g = (jax.random.normal(kk_, (b, h, s, d), jnp.float32)
                      .astype(dt) for kk_ in (kq, kk, kv, kg))

        def kern(q_, k_, v_):
            return fa.flash_attention_bhsd(q_, k_, v_, is_causal=True)

        def ref(q_, k_, v_):
            return fa._attention_ref(q_, k_, v_, None, True, 0.0)

        def grad_of(attn):
            # g is an ARGUMENT: a closed-over array becomes a constant of
            # the program (a quarter of a gigabyte here)
            return jax.jit(jax.grad(
                lambda q_, k_, v_, g_: jnp.sum(
                    attn(q_, k_, v_).astype(jnp.float32)
                    * g_.astype(jnp.float32)), argnums=(0, 1, 2)))

        out = jax.jit(kern)(q, k, v)
        grads = grad_of(kern)(q, k, v, g)
        assert_on((out, grads), devices[:1])
        hc = min(8, h)
        ref_fwd, ref_bwd = jax.jit(ref), grad_of(ref)
        err_fwd = err_bwd = 0.0
        for i in range(0, h, hc):
            sl = (slice(None), slice(i, i + hc))
            want = ref_fwd(q[sl], k[sl], v[sl])
            err_fwd = max(err_fwd, float(jnp.max(jnp.abs(
                out[sl].astype(jnp.float32) - want.astype(jnp.float32)))))
            np.testing.assert_allclose(
                np.asarray(out[sl], np.float32),
                np.asarray(want, np.float32), rtol=tol, atol=tol)
            wg = ref_bwd(q[sl], k[sl], v[sl], g[sl])
            for got_g, want_g in zip(grads, wg):
                scale = float(jnp.max(jnp.abs(want_g.astype(jnp.float32))))
                e = float(jnp.max(jnp.abs(
                    got_g[sl].astype(jnp.float32)
                    - want_g.astype(jnp.float32)))) / max(scale, 1e-6)
                err_bwd = max(err_bwd, e)
        if err_bwd > tol:
            raise AssertionError(
                f"flash attention gradients off by {err_bwd} of the "
                f"reference's largest entry (tolerance {tol})")

        x = jax.random.normal(kx, (b * s, c.hidden_size),
                              jnp.float32).astype(dt)
        w = 1.0 + 0.1 * jax.random.normal(kg, (c.hidden_size,), jnp.float32)
        got = jax.jit(lambda x_, w_: rn.rms_norm(x_, w_, c.rms_norm_eps))(
            x, w)
        want = jax.jit(lambda x_, w_: rn._rms_norm_ref(
            x_, w_, c.rms_norm_eps))(x, w)
        assert_on(got, devices[:1])
        err_rms = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
    ref_delta = dispatches_since(ref0)
    check_no_reference_dispatch(cfg, ref_delta, "the kernels' own calls")
    return {"shape_bhsd": [b, h, s, d], "dtype": c.dtype, "tolerance": tol,
            "flash_fwd_max_abs_err": err_fwd,
            "flash_bwd_max_rel_err": err_bwd,
            "rms_norm_max_abs_err": err_rms,
            "reference_dispatches": sum(ref_delta.values())}


def dense_logits(model, seq, pad_to: int):
    """forward_dense logits [len(seq), V] (float32, on the host) with the
    ids right-padded to one fixed length — causal attention leaves the
    real positions untouched, and one shape compiles once."""
    import numpy as np

    import paddle_tpu as paddle

    ids = np.zeros((1, pad_to), np.int64)
    ids[0, :len(seq)] = seq
    with paddle.no_grad():
        logits = model.forward_dense(paddle.to_tensor(ids))
    return np.asarray(logits.numpy()[0, :len(seq)], np.float32)


def abstract_step_args(engine, scfg, abstract=None, tokens=None):
    """The arguments of the engine's step programs (`serving_step`,
    `serving_fresh_prefill`, `serving_spec_verify`) as shapes, to lower
    them with: `(params, buffers, tokens, enc, dec, this, cu, block
    tables, key stack, value stack)`, and for a model with row state its
    stacks and the rows' slots. `tokens`: the step's token length,
    the token budget by default. `abstract` maps an array to the
    `ShapeDtypeStruct` to lower with (tools/tpu_compile_smoke.py and
    tests/test_tpu_compile.py hand the described chip's sharding); by
    default the arrays' own."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import scopes

    abstract = abstract or scopes.abstract
    b1 = scfg.max_batch + 1

    def i32(*shape):
        return abstract(jnp.zeros(shape, jnp.int32))

    return (jax.tree.map(abstract, engine._params),
            jax.tree.map(abstract, engine._buffers),
            i32(tokens or scfg.token_budget), i32(b1), i32(b1), i32(b1),
            i32(b1 + 1), i32(b1, scfg.max_blocks_per_seq),
            abstract(engine._kc), abstract(engine._vc),
            *((*map(abstract, engine._row_state.values()), i32(b1))
              if engine._row_state else ()))


def abstract_window_args(engine, scfg, rows, n, abstract=None):
    """The arguments of `engine._decode_window_fn(rows, n, "greedy")` as
    shapes: the step's, with `rows` tokens, then no scales, the samplers'
    three rows and the `[n, B + 1]` salts."""
    import jax.numpy as jnp

    from paddle_tpu.profiler import scopes

    abstract = abstract or scopes.abstract
    b1 = scfg.max_batch + 1
    f32, i32 = (abstract(jnp.zeros(b1, d)) for d in (jnp.float32, jnp.int32))
    return (*abstract_step_args(engine, scfg, abstract, rows), (), f32, i32,
            f32, abstract(jnp.zeros((n, b1), jnp.int32)))


def phase_serve(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """ServingEngine.from_model over a PagedCausalLM: requests of unequal
    prompt length, the last cfg.n_late admitted while the others decode;
    every request finishes; greedy streams are compared with greedy
    decoding through forward_dense on the same device.

    Parity has two readings. `streams_equal`: the engine's stream equals
    the reference's free-running greedy stream token for token. Where
    the engine runs bf16 against the float32 reference, near-ties of the
    top two logits can flip a token, after which free-running streams
    differ for good; so the criterion that decides the phase is
    teacher-forced: at EVERY generated position, given the engine's own
    prefix, the engine's token is within `logit_tol` of the reference's
    best logit. The tokens before a stream's first divergence are equal
    by construction and counted."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    ref0 = reference_dispatches()
    paddle.seed(cfg.seed)
    scfg = PagedServingConfig(**cfg.serving)
    with jax.default_device(devices[0]):
        model = PagedCausalLM(scfg)
    model.eval()
    assert_on([p._value for p in model.parameters()], devices[:1])
    rng = np.random.RandomState(cfg.seed + 1)
    prompts = [rng.randint(1, scfg.vocab_size, n).tolist()
               for n in cfg.prompt_lens]

    t0 = time.perf_counter()
    engine = ServingEngine.from_model(model, scfg, seed=cfg.seed)
    assert_on((engine._params, engine._kc, engine._vc), devices[:1])
    n_early = len(prompts) - cfg.n_late
    rids = [engine.add_request(p, max_new_tokens=cfg.max_new)
            for p in prompts[:n_early]]
    # run until every early request is decoding, then a few decode steps
    steps = 0
    while any(r.cached < len(r.prompt) for r in engine.pending()):
        engine.step()
        steps += 1
    for _ in range(3):
        engine.step()
        steps += 1
    decoding_at_late_admit = sum(len(r.generated) > 0
                                 for r in engine.pending())
    rids += [engine.add_request(p, max_new_tokens=cfg.max_new)
             for p in prompts[n_early:]]
    while any(r.cached < len(r.prompt) for r in engine.pending()):
        engine.step()
        steps += 1
    # every row at its decode tip: one device-side window, then the rest
    window = engine.decode_run(8)
    outs = engine.run_to_completion()
    serve_s = time.perf_counter() - t0
    if engine.pending():
        raise AssertionError(f"{len(engine.pending())} request(s) pending")
    for rid in rids:
        if len(outs[rid]) != cfg.max_new:
            raise AssertionError(
                f"request {rid} produced {len(outs[rid])} tokens, "
                f"expected {cfg.max_new}")
    if len(engine._free_pages) != scfg.num_blocks - 1:
        raise AssertionError("KV pages leaked")
    engine_refs = dispatches_since(ref0)
    check_no_reference_dispatch(cfg, engine_refs, "the engine's path")
    # the mixed step as the engine ran it, compiled again from its shapes
    # (a hit in the compilation cache): one paged-attention kernel and one
    # page-write kernel a layer when the configuration expects kernels,
    # none otherwise
    calls = kernel_calls_in(engine._compiled.lower(
        *abstract_step_args(engine, scfg)).compile().as_text())
    want = dict.fromkeys(("paged_attention", "kv_page_write"),
                         scfg.num_layers if cfg.kernels else 0)
    if {k: calls[k] for k in want} != want:
        raise AssertionError(
            f"kernel calls in the engine's mixed step: {calls}, want {want}")
    ref1 = reference_dispatches()

    # -- the reference: forward_dense, teacher-forced -------------------
    # One pass over prompt + the engine's tokens gives the reference's
    # logits at every generated position given the engine's prefix. The
    # reference's own free-running greedy stream is read off the same
    # pass: it equals the engine's up to the first position where the
    # reference's argmax differs (identical prefixes until then).
    bf16 = scfg.dtype == "bfloat16"
    streams_equal, equal_prefix, worst_gap, scale = 0, [], 0.0, 0.0
    t0 = time.perf_counter()
    for rid, prompt in zip(rids, prompts):
        got = np.asarray(outs[rid])
        logits = dense_logits(model, prompt + outs[rid], scfg.max_seq)
        at = logits[len(prompt) - 1:len(prompt) - 1 + len(got)]
        gaps = at.max(axis=-1) - at[np.arange(len(got)), got]
        worst_gap = max(worst_gap, float(gaps.max()))
        scale = max(scale, float(at.std()))
        differs = np.flatnonzero(at.argmax(axis=-1) != got)
        n_eq = int(differs[0]) if differs.size else len(got)
        equal_prefix.append(n_eq)
        streams_equal += n_eq == len(got)
    ref_s = time.perf_counter() - t0
    logit_tol = (4 * BF16_TOL if bf16 else 1e-3) * scale
    if worst_gap > logit_tol:
        raise AssertionError(
            f"engine token {worst_gap:.4f} below the reference's best "
            f"logit (tolerance {logit_tol:.4f}, logit std {scale:.3f})")
    if not bf16 and streams_equal != len(rids):
        raise AssertionError(
            f"float32 engine streams differ from forward_dense greedy: "
            f"equal prefixes {equal_prefix}")
    return {"layers": scfg.num_layers, "hidden": scfg.hidden_size,
            "dtype": scfg.dtype, "requests": len(rids),
            "prompt_lens": list(cfg.prompt_lens), "max_new": cfg.max_new,
            "finished": len(rids), "steps_before_window": steps,
            "decoding_at_late_admit": decoding_at_late_admit,
            "window_tokens": len(window),
            "tokens_generated": sum(len(outs[r]) for r in rids),
            "streams_equal": streams_equal,
            "equal_prefix_tokens": equal_prefix,
            "teacher_forced_worst_logit_gap": worst_gap,
            "logit_tolerance": logit_tol, "logit_std": scale,
            "serve_s": round(serve_s, 2), "reference_s": round(ref_s, 2),
            **device_memory(devices[0]),
            "kernel_calls": calls,
            "reference_dispatches": sum(engine_refs.values()),
            # forward_dense pads to max_seq rows, which rms_norm's 256-row
            # blocks need not divide: the REFERENCE may run jnp rms_norm
            "dense_reference_dispatches": dispatches_since(ref1)}


def phase_serve_hybrid(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """ServingEngine.from_model over the small hybrid model
    (models/nemotron_h.py): state-space layers over a row slot beside one
    paged-attention layer and an expert layer that holds a share. Requests
    of unequal length, the last cfg.n_late admitted while the others
    decode; every request finishes, every slot and page comes back; the
    compiled mixed step holds one state-update kernel a state-space layer
    and one paged-attention kernel, and nothing gave way to a reference;
    teacher-forced, every served token lies within `logit_tol` of the
    plain reference's best (benchmark/reference/nemotron_h_serve.py)."""
    import jax
    import numpy as np

    from benchmark.reference import nemotron_h_serve as plain
    from paddle_tpu.inference.serving import (PagedServingConfig,
                                              ServingEngine)
    from paddle_tpu.models.nemotron_h import (NemotronH, NemotronHSpec,
                                              init_params)

    ref0 = reference_dispatches()
    sizes = dict(cfg.hybrid)
    spec = NemotronHSpec.from_config(sizes, held=sizes.pop("held"))
    scfg = PagedServingConfig(**cfg.hybrid_serving)
    with jax.default_device(devices[0]):
        model = NemotronH(spec, init_params(spec, cfg.seed))
    rng = np.random.RandomState(cfg.seed + 2)
    prompts = [rng.randint(1, scfg.vocab_size, n).tolist()
               for n in cfg.prompt_lens]
    t0 = time.perf_counter()
    engine = ServingEngine.from_model(model, scfg, seed=cfg.seed)
    assert_on((engine._params, engine._kc, engine._row_state), devices[:1])
    n_early = len(prompts) - cfg.n_late
    rids = [engine.add_request(p, max_new_tokens=cfg.max_new)
            for p in prompts[:n_early]]
    steps = 0
    while any(r.cached < len(r.prompt) for r in engine.pending()):
        engine.step()
        steps += 1
    for _ in range(3):
        engine.step()
        steps += 1
    decoding_at_late_admit = sum(len(r.generated) > 0
                                 for r in engine.pending())
    rids += [engine.add_request(p, max_new_tokens=cfg.max_new)
             for p in prompts[n_early:]]
    outs = engine.run_to_completion()
    serve_s = time.perf_counter() - t0
    if engine.pending() or any(len(outs[r]) != cfg.max_new for r in rids):
        raise AssertionError("a request did not finish")
    if len(engine._free_pages) != scfg.num_blocks - 1 \
            or len(engine._free_slots) != scfg.max_batch:
        raise AssertionError("pages or row slots leaked")
    engine_refs = dispatches_since(ref0)
    check_no_reference_dispatch(cfg, engine_refs, "the hybrid engine's path")
    calls = kernel_calls_in(engine._compiled.lower(
        *abstract_step_args(engine, scfg)).compile().as_text())
    want = {"ssm_state_update": spec.count("M") if cfg.kernels else 0,
            "paged_attention": spec.count("*") if cfg.kernels else 0,
            "kv_page_write": spec.count("*") if cfg.kernels else 0}
    if {k: calls[k] for k in want} != want:
        raise AssertionError(
            f"kernel calls in the hybrid mixed step: {calls}, want {want}")

    first, count = spec.held
    plain_cfg = dict(cfg.hybrid, experts_held={
        "first": first, "count": count, "of": spec.n_routed_experts})
    worst_gap, scale, streams_equal = 0.0, 0.0, 0
    t0 = time.perf_counter()
    for rid, prompt in zip(rids, prompts):
        got = np.asarray(outs[rid])
        at = np.asarray(plain.logits_at(
            model.params, prompt + outs[rid], len(prompt) - 1, plain_cfg,
            pad_to=scfg.max_seq))[:len(got)]
        gaps = at.max(axis=-1) - at[np.arange(len(got)), got]
        worst_gap = max(worst_gap, float(gaps.max()))
        scale = max(scale, float(at.std()))
        streams_equal += bool((at.argmax(axis=-1) == got).all())
    ref_s = time.perf_counter() - t0
    bf16 = scfg.dtype == "bfloat16"
    logit_tol = (4 * BF16_TOL if bf16 else 1e-3) * scale
    if worst_gap > logit_tol:
        raise AssertionError(
            f"hybrid engine token {worst_gap:.4f} below the reference's "
            f"best logit (tolerance {logit_tol:.4f}, logit std {scale:.3f})")
    return {"pattern": spec.hybrid_override_pattern,
            "hidden": spec.hidden_size, "dtype": scfg.dtype,
            "requests": len(rids), "finished": len(rids), "steps": steps,
            "decoding_at_late_admit": decoding_at_late_admit,
            "tokens_generated": sum(len(outs[r]) for r in rids),
            "streams_equal": streams_equal,
            "teacher_forced_worst_logit_gap": worst_gap,
            "logit_tolerance": logit_tol, "logit_std": scale,
            "serve_s": round(serve_s, 2), "reference_s": round(ref_s, 2),
            **device_memory(devices[0]), "kernel_calls": calls,
            "reference_dispatches": sum(engine_refs.values())}


def phase_eager(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """The path core/place.py and core/tensor.py decide: eager ops and one
    jit.TrainStep on the default place, every array on devices[0]."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    dev = devices[0]
    want_place = "cpu" if dev.platform == "cpu" else f"tpu:{dev.id}"
    if paddle.get_device() != want_place:
        raise AssertionError(
            f"paddle.get_device() is {paddle.get_device()!r}, expected "
            f"{want_place!r}")
    paddle.seed(cfg.seed)
    rng = np.random.RandomState(cfg.seed)
    a_np = rng.randn(64, 128).astype(np.float32)
    b_np = rng.randn(128, 32).astype(np.float32)
    a, b = paddle.to_tensor(a_np), paddle.to_tensor(b_np)
    # f32 matmuls run as bf16 passes on the MXU at default precision
    mm_tol = BF16_TOL if dev.platform == "tpu" else 1e-4
    checks = [
        ("add", a + a, a_np + a_np, 1e-6),
        ("mul", a * 2.0, a_np * 2.0, 1e-6),
        ("matmul", paddle.matmul(a, b), a_np @ b_np, mm_tol * 16),
        ("relu", nn.functional.relu(a), np.maximum(a_np, 0), 1e-6),
        ("exp", paddle.exp(a), np.exp(a_np), 1e-3),
        ("sum", paddle.sum(a, axis=1), a_np.sum(1), 1e-3),
        ("mean", paddle.mean(a), a_np.mean(), 1e-5),
        ("reshape", paddle.reshape(a, [128, 64]),
         a_np.reshape(128, 64), 0),
        ("transpose", paddle.transpose(a, [1, 0]), a_np.T, 0),
        ("concat", paddle.concat([a, a], axis=0),
         np.concatenate([a_np, a_np]), 0),
        ("argmax", paddle.argmax(a, axis=1), a_np.argmax(1), 0),
        ("softmax", nn.functional.softmax(a, axis=-1),
         np.exp(a_np - a_np.max(-1, keepdims=True))
         / np.exp(a_np - a_np.max(-1, keepdims=True)).sum(-1,
                                                          keepdims=True),
         1e-4),
        # a directly attached TPU has complex types: no host detour
        ("fft", paddle.fft.fft(a), np.fft.fft(a_np), 1e-2),
    ]
    for name, got, want, tol in checks:
        assert_on(got._value, [dev])
        np.testing.assert_allclose(got.numpy(), want, rtol=tol, atol=tol,
                                   err_msg=name)

    model = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 10))
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-2)
    step = TrainStep(model, nn.CrossEntropyLoss(), opt)
    x = paddle.to_tensor(rng.randn(16, 32).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (16,)).astype(np.int64))
    losses = [float(step(x, y).numpy()) for _ in range(3)]
    if getattr(step, "_fallback", False):
        raise AssertionError("TrainStep fell back to eager execution")
    if not (all(np.isfinite(losses)) and losses[-1] < losses[0]):
        raise AssertionError(f"TrainStep losses {losses}")
    assert_on([p._value for p in model.parameters()], [dev])
    return {"device": paddle.get_device(),
            "ops": [name for name, *_ in checks],
            "fft_dtype": str(paddle.fft.fft(a).numpy().dtype),
            "trainstep_losses": losses}


def phase_multichip(cfg: SmokeConfig, devices) -> Dict[str, Any]:
    """The same configuration, seed and batch on devices[:1] and on all of
    `devices` (each of LAYOUTS), in this one process, at an even depth.
    The loss
    trajectories agree within bf16 tolerance, and each parameter's
    addressable shards lie on all the devices with an even share of the
    bytes. The sharded runs START FROM THE ONE-CHIP RUN'S PARAMETERS
    (copied through the host): the framework may pick a PRNG whose bits
    depend on the sharding, so equal seeds alone do not promise equal
    weights — `own_init_equal` reports whether they were."""
    import gc

    import jax
    import numpy as np

    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.distributed.topology import build_mesh

    n = len(devices)
    model = even_depth(cfg.llama)
    one = HybridTrainer(model, build_mesh(devices=devices[:1]),
                        seed=cfg.seed)
    init_host = jax.device_get(one.params)
    ref_losses, ref_secs = train_steps(one, cfg)
    del one
    gc.collect()

    tol = BF16_TOL if cfg.llama.dtype == "bfloat16" else 5e-3
    runs = []
    for layout, options in LAYOUTS:
        mesh = build_mesh(devices=devices, **layout)
        if mesh.size != n:
            raise AssertionError(f"layout {layout} is not {n} devices")
        tr = HybridTrainer(model, mesh, seed=cfg.seed, **options)
        own_init_equal = all(
            np.array_equal(np.asarray(jax.device_get(a)), b)
            for a, b in zip(jax.tree.leaves(tr.params),
                            jax.tree.leaves(init_host)))
        tr.params = jax.tree.map(jax.device_put, init_host,
                                 tr.param_shardings)
        share = check_placement(tr, devices)
        ref0 = reference_dispatches()
        calls = kernel_calls_in(tr.lower((cfg.batch, cfg.seq)).as_text())
        check_kernels(cfg, calls, dispatches_since(ref0))
        losses, secs = train_steps(tr, cfg)
        worst = max(abs(a - b) / max(1.0, abs(b))
                    for a, b in zip(losses, ref_losses))
        if worst > tol:
            raise AssertionError(
                f"{layout}: losses {losses} differ from one-chip "
                f"{ref_losses} by {worst:.4f} (tol {tol})")
        runs.append({"layout": layout, "pipelined": tr.pipelined,
                     "losses": losses,
                     "worst_rel_diff": worst,
                     "own_init_equal": own_init_equal,
                     "param_bytes_share_per_device": share,
                     "kernel_calls": calls,
                     "first_step_s": round(secs[0], 3),
                     "step_s": round(min(secs[1:]), 4),
                     "memory": [device_memory(d) for d in devices]})
        del tr
        gc.collect()
    return {"devices": n, "layers": model.num_hidden_layers,
            "seq": cfg.seq, "batch": cfg.batch, "tolerance": tol,
            "one_chip": {"losses": ref_losses,
                         "first_step_s": round(ref_secs[0], 3),
                         "step_s": round(min(ref_secs[1:]), 4)},
            "runs": runs}


def main(argv=None) -> int:
    args = parse_args(argv)          # no backend exists before this line

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = require_tpu(args.chips)
    cfg = chip_config(args.seed)
    emit("start", {"compile_cache_dir": cache_dir, "seed": args.seed,
                   "chips": args.chips})
    if args.chips == 4:
        emit("multichip", phase_multichip(cfg, devices))
    else:
        for name, phase in (("train", phase_train),
                            ("kernels", phase_kernels),
                            ("serve", phase_serve),
                            ("serve_hybrid", phase_serve_hybrid),
                            ("eager", phase_eager)):
            emit(name, phase(cfg, devices))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
