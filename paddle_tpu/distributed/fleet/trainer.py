"""Hybrid-parallel compiled trainer for the stacked Llama core.

Reference analog: the whole Fleet static-graph training pipeline —
distributed_optimizer + pipeline/sharding passes + PirInterpreter. On TPU it
is ONE pjit'd function: parameters carry PartitionSpecs over the
[dp, pp, sharding, sep, mp] mesh (models.llama.param_specs), the batch is
sharded over the data axes, optimizer states inherit parameter shardings,
and XLA GSPMD inserts + overlaps every collective (grad psum over dp,
all-gathers for FSDP 'sharding', TP collectives over 'mp', cross-stage
transfers over 'pp').
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ...models import llama as llama_mod
from ...profiler import scopes as _scopes
from ...profiler import tracing as _tracing

__all__ = ["HybridTrainer", "data_spec"]


def data_spec():
    """Batch sharding: batch dim over the data axes (dp + sharding acts as
    the FSDP data axis), sequence dim over 'sep' (context parallel)."""
    return P(("dp", "sharding"), "sep")


class HybridTrainer:
    """AdamW trainer over the stacked Llama core with full hybrid
    shardings. Usage:

        trainer = HybridTrainer(config, mesh)
        loss = trainer.step(input_ids, labels)   # one fused XLA step
    """

    def __init__(self, config, mesh: Mesh, learning_rate=3e-4,
                 weight_decay=0.1, beta1=0.9, beta2=0.95, eps=1e-8,
                 grad_clip_norm: Optional[float] = 1.0, seed: int = 0,
                 remat: bool = True,
                 pipeline_micro_batches: Optional[int] = None,
                 overlap_sends: bool = False):
        self.config = config
        self.mesh = mesh
        self.lr = learning_rate
        self.wd = weight_decay
        self.betas = (beta1, beta2)
        self.eps = eps
        self.clip = grad_clip_norm
        self.remat = remat
        # latency-hidden pipeline sends (spmd_pipeline overlap_sends):
        # each tick's micro-batch half-splits so the first half's ICI hop
        # runs behind the second half's compute
        self.overlap_sends = overlap_sends
        # pp>1 + micro-batches => schedule-driven compiled pipeline
        # (spmd_pipeline ring inside shard_map); otherwise the pp axis is a
        # pure GSPMD layer-stack placement.
        pp = mesh.shape.get("pp", 1)
        self.n_micro = int(pipeline_micro_batches or 1)
        self.pipelined = pp > 1 and self.n_micro > 1
        if self.n_micro > 1 and pp <= 1:
            raise ValueError(
                f"pipeline_micro_batches={self.n_micro} requires a mesh "
                f"with a 'pp' axis of size > 1 (got pp={pp})")
        if self.pipelined and config.num_hidden_layers % pp != 0:
            raise ValueError(
                f"num_hidden_layers={config.num_hidden_layers} must divide "
                f"evenly over pp={pp} for the compiled pipeline")

        specs = llama_mod.param_specs(config)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        self._init_state(seed)
        self.step_count = 0
        self._compiled = self._build()
        self._registered = False    # with profiler.scopes, at first step

    def _init_state(self, seed: int):
        """Materialize `params` and `opt_state` directly INTO the sharded
        layout (no host-side full copy). The one step of construction
        that needs real devices: tests/test_tpu_compile.py overrides it
        with shapes to compile the step for a described chip."""
        init = jax.jit(
            functools.partial(llama_mod.init_stacked_params, self.config),
            out_shardings=self.param_shardings)
        self.params = init(jax.random.key(seed))
        self.opt_state = jax.jit(
            lambda p: {
                "m": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                  p),
                "v": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                  p),
            },
            out_shardings={"m": self.param_shardings,
                           "v": self.param_shardings},
        )(self.params)

    def _build(self):
        cfg = self.config
        b1, b2 = self.betas
        eps = self.eps
        wd = self.wd
        clip = self.clip
        remat = self.remat
        mesh = self.mesh
        pipelined = self.pipelined
        overlap_sends = self.overlap_sends
        spec = llama_mod.microbatch_spec() if pipelined else data_spec()
        batch_sharding = NamedSharding(self.mesh, spec)

        def train_step(params, opt_state, input_ids, labels, lr, t):
            if pipelined:
                loss_of = lambda p: llama_mod.loss_fn_pipelined(  # noqa: E731
                    p, (input_ids, labels), cfg, mesh, remat=remat,
                    overlap_sends=overlap_sends)
            else:
                # the mesh rides along: sep>1 runs ring-attention context
                # parallel inside the trunk, and the Pallas kernels run
                # per shard on a multi-device mesh
                loss_of = lambda p: llama_mod.loss_fn_stacked(  # noqa: E731
                    p, (input_ids, labels), cfg, remat=remat, mesh=mesh)
            loss, grads = jax.value_and_grad(loss_of)(params)
            with _scopes.scope("clip"):
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                if clip is not None:
                    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                         for g in jax.tree.leaves(grads)))
                    scale = jnp.minimum(
                        1.0, clip / jnp.maximum(gnorm, 1e-12))
                    grads = jax.tree.map(lambda g: g * scale, grads)

            def upd(p, g, m, v):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                mhat = m / (1 - b1 ** t)
                vhat = v / (1 - b2 ** t)
                step = mhat / (jnp.sqrt(vhat) + eps) \
                    + wd * p.astype(jnp.float32)
                return (p.astype(jnp.float32) - lr * step).astype(p.dtype), \
                    m, v
            with _scopes.scope("adamw"):
                out = jax.tree.map(upd, params, grads, opt_state["m"],
                                   opt_state["v"])
            new_p = jax.tree.map(lambda o: o[0], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            new_m = jax.tree.map(lambda o: o[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            new_v = jax.tree.map(lambda o: o[2], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            return new_p, {"m": new_m, "v": new_v}, loss

        return jax.jit(
            train_step,
            in_shardings=(self.param_shardings,
                          {"m": self.param_shardings,
                           "v": self.param_shardings},
                          batch_sharding, batch_sharding, None, None),
            out_shardings=(self.param_shardings,
                           {"m": self.param_shardings,
                            "v": self.param_shardings},
                           None),
            donate_argnums=(0, 1),
        )

    def place_batch(self, input_ids, labels):
        ids, labs = jnp.asarray(input_ids), jnp.asarray(labels)
        if self.pipelined:
            b = ids.shape[0]
            if b % self.n_micro != 0:
                raise ValueError(
                    f"batch {b} not divisible by "
                    f"pipeline_micro_batches={self.n_micro}")
            mb = b // self.n_micro
            ids = ids.reshape((self.n_micro, mb) + ids.shape[1:])
            labs = labs.reshape((self.n_micro, mb) + labs.shape[1:])
            sharding = NamedSharding(self.mesh, llama_mod.microbatch_spec())
        else:
            sharding = NamedSharding(self.mesh, data_spec())
        return (jax.device_put(ids, sharding),
                jax.device_put(labs, sharding))

    def step(self, input_ids, labels):
        with _tracing.span("trainer::step"):
            with _tracing.phase("trainer::place_batch"):
                ids, labs = self.place_batch(input_ids, labels)
            self.step_count += 1
            args = (self.params, self.opt_state, ids, labs,
                    jnp.asarray(self.lr, jnp.float32),
                    jnp.asarray(self.step_count, jnp.float32))
            if not self._registered:
                # shapes and shardings only (the step's closure holds no
                # array either), so the entry outlives this trainer: the
                # phase map is asked for once the run is over, and the
                # newest trainer's step is the one kept under the name
                self._registered = True
                _scopes.register_program("train_step", self._compiled,
                                         _scopes.abstract(args))
            with _tracing.phase("trainer::dispatch"):
                self.params, self.opt_state, loss = self._compiled(*args)
        return loss

    # -- elastic supervisor wiring (distributed/resilience/supervisor) -----
    def _flat_np(self, tree, prefix: str) -> Dict[str, np.ndarray]:
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {prefix + jax.tree_util.keystr(kp):
                np.asarray(jax.device_get(v)) for kp, v in leaves}

    def elastic_state(self) -> Dict[str, np.ndarray]:
        """Flat host-side state dict (params + Adam moments + step) —
        the unit the elastic supervisor snapshots to its ring neighbor
        and the disk tier."""
        d = {**self._flat_np(self.params, "p:"),
             **self._flat_np(self.opt_state["m"], "m:"),
             **self._flat_np(self.opt_state["v"], "v:")}
        d["step"] = np.asarray(self.step_count, np.int64)
        return d

    def load_elastic_state(self, state: Dict[str, np.ndarray]):
        """Restore from ``elastic_state()`` output, device_put-ing every
        leaf back onto its CURRENT NamedSharding — the reshard-on-load
        path, so a snapshot taken under one topology restores under
        another."""
        def fill(tree, prefix):
            kps, treedef = jax.tree_util.tree_flatten_with_path(tree)
            shardings = jax.tree_util.tree_leaves(self.param_shardings)
            new = []
            for (kp, leaf), sh in zip(kps, shardings):
                src = np.asarray(state[prefix + jax.tree_util.keystr(kp)])
                new.append(jax.device_put(src.astype(leaf.dtype), sh))
            return jax.tree_util.tree_unflatten(treedef, new)

        self.params = fill(self.params, "p:")
        self.opt_state = {"m": fill(self.opt_state["m"], "m:"),
                          "v": fill(self.opt_state["v"], "v:")}
        self.step_count = int(np.asarray(state["step"]))

    def run_elastic(self, batch_fn: Callable, num_steps: int,
                    config=None, **overrides):
        """Drive this trainer under the self-healing supervisor:
        `batch_fn(step) -> (input_ids, labels)` must be deterministic in
        `step` so replay after a rollback/recovery converges. Returns
        the supervisor's (final_state, report)."""
        from ..resilience.supervisor import (SupervisorConfig,
                                             run_elastic)

        cfg = config or SupervisorConfig.from_env(**overrides)

        def step_fn(state, step, ctx):
            ids, labels = batch_fn(step)
            loss = self.step(ids, labels)
            return self.elastic_state(), float(np.asarray(
                jax.device_get(loss)))

        return run_elastic(step_fn, self.elastic_state(), cfg,
                           num_steps=num_steps,
                           on_restore=self.load_elastic_state,
                           start_step=self.step_count)

    def lower(self, batch_shape):
        """The train step lowered for `batch_shape` ([B, S]) without
        running it: `.as_text()` shows the program (shardings, kernels),
        `.compile().memory_analysis()` what it needs on each device."""
        if self.pipelined and len(batch_shape) == 2:
            b, s = batch_shape
            if b % self.n_micro != 0:
                raise ValueError(
                    f"batch {b} not divisible by "
                    f"pipeline_micro_batches={self.n_micro}")
            batch_shape = (self.n_micro, b // self.n_micro, s)
        spec = llama_mod.microbatch_spec() if self.pipelined \
            else data_spec()
        ids = jax.ShapeDtypeStruct(
            batch_shape, jnp.int32,
            sharding=NamedSharding(self.mesh, spec))
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        return self._compiled.lower(
            self.params, self.opt_state, ids, ids, scalar, scalar)
