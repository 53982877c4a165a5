"""The training runner: `HybridTrainer.step` back to back.

Set-up builds ONE trainer, gives it the benchmark's weights, drives it
through its first three steps by the window's own call and feed (the
readings that decide `correct` are taken there), and hands that same
object to the window. The window ends on `block_until_ready` of its last
step. The plain reference follows once the window has closed, the memory
peak has been read and the trainer is freed.
"""
from __future__ import annotations

import gc
import math
import time

from .. import compare, traffic_gen, weights
from ..harness import (CompileCounter, RunResult, Tracer, annotate,
                       load_module, memory_peak_bytes)


def build_trainer(config: dict, devices, seed: int):
    """The program under test, as a user builds it."""
    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models.llama import LlamaConfig

    if config["sliding_window"] < config["assumed"]["max_context"]:
        raise ValueError("the trainer has no sliding-window attention")
    t = config["trainer"]
    llama = LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["assumed"]["max_context"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"],
        use_flash_attention=t["use_flash_attention"],
        recompute=t["recompute"], remat_policy=t["remat_policy"])
    o = config["optimizer"]
    return HybridTrainer(
        llama, build_mesh(devices=list(devices), **config["mesh"]),
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
        grad_clip_norm=o["grad_clip_norm"], seed=seed & 0x7FFFFFFF,
        remat=t["recompute"])


def first_steps(trainer, batches, config: dict, seed: int) -> dict:
    """Steps 1-3 through `trainer.step`, with the program's readings: each
    loss, each leaf's first gradient as the optimizer got it (its first
    moment after one step is (1 - beta1) g), and each leaf's change over
    the first two updates. The seed's weights are made again for that, by
    the very call that made them (between steps there is room for them).
    Made inside the jitted difference instead, XLA drops their rounding to
    bfloat16 and the rounding residue reads as change: 0.85% of the
    embedding's, on every seed (my chip runs, PR 24)."""
    import jax
    import jax.numpy as jnp

    ref = load_module("reference", config["reference"])
    norms = jax.jit(ref.leaf_norms)
    change_norms = jax.jit(lambda params, p0: ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, p0)))

    losses = [trainer.step(*batches[0])]
    grad = norms(trainer.opt_state["m"])
    losses.append(trainer.step(*batches[1]))
    p0 = weights.make_train_params(config, seed, trainer.param_shardings)
    change = change_norms(trainer.params, p0)
    del p0
    losses.append(trainer.step(*batches[2]))
    b1 = config["optimizer"]["beta1"]
    return {"loss": [float(x) for x in losses],
            "grad_norm": {k: float(v) / (1 - b1) for k, v in grad.items()},
            "change_norm": {k: float(v) for k, v in change.items()}}


def run(cell, seed: int, seconds: float, trace: bool, devices, t_start):
    import jax

    config, mix = cell.config, cell.traffic
    counter = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}   # imports, the chip
    trainer = build_trainer(config, devices, seed)
    jax.block_until_ready(trainer.params)
    phases["trainer"] = time.perf_counter() - t_start
    trainer.params = weights.make_train_params(config, seed,
                                               trainer.param_shardings)
    batches = traffic_gen.train_batches(mix, seed, config["vocab_size"])
    jax.block_until_ready(trainer.params)
    phases["weights_batches"] = time.perf_counter() - t_start
    program = first_steps(trainer, batches, config, seed)
    phases["first_steps"] = time.perf_counter() - t_start
    every = mix["loss_read_every"]
    tokens_per_step = mix["batch"] * mix["seq"]
    tracer = Tracer(trace, cell.name)

    # -- the window ------------------------------------------------------
    steps, traced_steps, loss = 0, 0, None
    counter.open = True
    tracer.start()
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        with annotate("trainer_step"):
            loss = trainer.step(*batches[(3 + steps) % len(batches)])
        steps += 1
        if steps % every == 0:
            with annotate("read_loss"):
                float(loss)
        if tracer.due():
            with annotate("sync"):
                loss.block_until_ready()
            tracer.stop()
            traced_steps = steps
    with annotate("sync"):
        loss.block_until_ready()
    t_close = time.perf_counter()
    if tracer.running:
        tracer.stop()
        traced_steps = steps
    compiles = counter.close()
    last_loss = float(loss)

    peak = memory_peak_bytes(devices)
    opt = config["optimizer"]
    del trainer
    gc.collect()

    # -- the reference, on the freed chip ---------------------------------
    ref = load_module("reference", config["reference"])
    reference = ref.first_steps(
        weights.make_train_params(config, seed), batches[:3], config, opt)
    compared, loose = compare.train_readings(program, reference,
                                             cell.limits)
    failed = 0 if math.isfinite(last_loss) else 1
    return RunResult(
        attempted=steps, failed=failed,
        end_to_end={
            "train_tokens_s_chip": steps * tokens_per_step
            / (t_close - t_open) / len(devices),
            "setup_s": t_open - t_start},
        compared=compared,
        stats={"traced_work": {"tokens": traced_steps * tokens_per_step,
                               "seq": mix["seq"]},
               "steps": steps, "window_s": t_close - t_open,
               "setup_phases_s": phases, "not_compared": loose,
               "program": program, "reference": reference},
        trace=tracer.load() if trace else None,
        memory_peak_bytes=peak, compiles_in_window=compiles)
