"""Auto-tuner: search over hybrid-parallel configs.

Reference analog: python/paddle/distributed/auto_tuner/ (tuner.py:21 grid
search, prune.py pruning rules, cost_model.py). Searches
dp/mp/pp/sharding/micro-batch configurations: candidates are enumerated and
pruned analytically (divisibility, memory model), then either ranked by the
cost model or measured by running user-supplied trials.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

__all__ = ["AutoTuner", "TunerCfg", "default_candidates", "prune_by_memory",
           "estimate_step_time", "estimate_memory_bytes"]


@dataclass
class TunerCfg:
    dp: int = 1
    mp: int = 1
    pp: int = 1
    sharding_degree: int = 1
    sharding_stage: int = 1
    micro_batch_size: int = 1
    recompute: bool = True

    def world(self):
        return self.dp * self.mp * self.pp * self.sharding_degree

    def as_dict(self):
        return dict(dp_degree=self.dp, mp_degree=self.mp, pp_degree=self.pp,
                    sharding_degree=self.sharding_degree,
                    sharding_stage=self.sharding_stage,
                    micro_batch_size=self.micro_batch_size,
                    recompute=self.recompute)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def default_candidates(num_devices: int, global_batch: int,
                       num_layers: int) -> List[TunerCfg]:
    """Grid enumeration with divisibility pruning (reference prune rules:
    product must equal world size; pp must divide layers; micro-bs must
    divide the per-dp batch)."""
    out = []
    for mp in _divisors(num_devices):
        for pp in _divisors(num_devices // mp):
            if num_layers % pp != 0:
                continue
            rest = num_devices // (mp * pp)
            for sh in _divisors(rest):
                dp = rest // sh
                per_dp = global_batch // max(dp * sh, 1)
                if per_dp == 0 or global_batch % max(dp * sh, 1) != 0:
                    continue
                for mbs in _divisors(per_dp):
                    for stage in ([1] if sh == 1 else [1, 2, 3]):
                        for rc in (True, False):
                            out.append(TunerCfg(dp, mp, pp, sh, stage, mbs,
                                                rc))
    return out


def estimate_memory_bytes(cfg: TunerCfg, n_params: int, hidden: int,
                          layers: int, seq: int, param_bytes: int = 2,
                          state_bytes: int = 8) -> float:
    """Per-chip memory model (reference cost_model.py shape): params split
    by mp*pp (and sharding at stage 3), optimizer states by sharding,
    activations by remat policy.

    Calibrated against XLA memory_analysis of the AdamW train step of
    Llama-2-13B-dimension blocks (hidden 5120 / 40 heads / seq 4096,
    bf16, flash attention) on a v5e chip across micro-batch 1-4, layer
    counts 1-2, and remat on/off (argument + temp bytes); the bar is
    tests/test_auto_tuner_model.py::test_memory_model_within_15pct_on_chip,
    run by tools/validate_memory_model.py."""
    shard_p = cfg.mp * cfg.pp * (cfg.sharding_degree
                                 if cfg.sharding_stage >= 3 else 1)
    shard_s = cfg.mp * cfg.pp * cfg.sharding_degree
    params = n_params * param_bytes / shard_p
    # grads materialize fully when a layer stack is scanned (stacked grad
    # arrays); with a single resident layer XLA aliases most grad buffers
    # straight into the optimizer update
    layers_here = max(layers / cfg.pp, 1)
    grad_frac = 1.0 if layers_here > 1 else 0.45
    grads = n_params * 4 * grad_frac / (cfg.mp * cfg.pp * (
        cfg.sharding_degree if cfg.sharding_stage >= 2 else 1))
    states = n_params * state_bytes / shard_s
    # activations per microbatch, in units of seq*hidden*2 bytes:
    # k_layer saved per extra layer (remat(save_attn) keeps the block
    # input + flash output; full saves every intermediate) + a k_base
    # backward working set for the active layer
    k_layer = 4 if cfg.recompute else 22
    k_base = 21
    acts = (cfg.micro_batch_size * seq * hidden * 2
            * (k_layer * max(layers_here - 1, 0) + k_base) / cfg.mp)
    return params + grads + states + acts


def estimate_step_time(cfg: TunerCfg, n_params: int, global_batch: int,
                       seq: int, chip_flops: float = 197e12,
                       ici_bw: float = 4.5e10) -> float:
    """Relative step-time cost: compute + pipeline bubble + TP comm."""
    tokens = global_batch * seq
    flops = 6 * n_params * tokens * (4 / 3 if cfg.recompute else 1.0)
    world = cfg.world()
    compute = flops / (world * chip_flops * 0.5)
    n_micro = max(global_batch // (cfg.dp * cfg.sharding_degree
                                   * cfg.micro_batch_size), 1)
    bubble = (cfg.pp - 1) / (n_micro + cfg.pp - 1) if cfg.pp > 1 else 0.0
    compute = compute / max(1 - bubble, 1e-3)
    # TP allreduce volume per step ~ params-scale activations over mp
    comm = 0.0
    if cfg.mp > 1:
        comm = 4 * tokens / (cfg.dp * cfg.sharding_degree) \
            * 4096 * 2 / ici_bw * (cfg.mp - 1) / cfg.mp
    return compute + comm


class AutoTuner:
    """reference tuner.py:21. Analytic ranking + optional measured trials."""

    def __init__(self, num_devices: int, global_batch: int, n_params: int,
                 hidden: int, layers: int, seq: int,
                 hbm_bytes: float = 16e9, max_trials: int = 10):
        self.num_devices = num_devices
        self.global_batch = global_batch
        self.n_params = n_params
        self.hidden = hidden
        self.layers = layers
        self.seq = seq
        self.hbm = hbm_bytes
        self.max_trials = max_trials
        self.history: List[tuple] = []

    def candidates(self) -> List[TunerCfg]:
        cands = default_candidates(self.num_devices, self.global_batch,
                                   self.layers)
        cands = [c for c in cands if c.world() == self.num_devices]
        return prune_by_memory(cands, self)

    def rank(self) -> List[TunerCfg]:
        cands = self.candidates()
        cands.sort(key=lambda c: estimate_step_time(
            c, self.n_params, self.global_batch, self.seq))
        return cands

    def tune(self, trial_fn: Optional[Callable[[TunerCfg], float]] = None
             ) -> TunerCfg:
        """trial_fn(cfg) -> measured step time; None = analytic only."""
        ranked = self.rank()
        if not ranked:
            raise RuntimeError("no feasible configuration (memory model "
                               "rejects all candidates)")
        if trial_fn is None:
            return ranked[0]
        best, best_t = None, float("inf")
        for cfg in ranked[: self.max_trials]:
            try:
                t = trial_fn(cfg)
            except Exception:
                continue
            self.history.append((cfg, t))
            if t < best_t:
                best, best_t = cfg, t
        return best or ranked[0]


def prune_by_memory(cands: List[TunerCfg], tuner: AutoTuner
                    ) -> List[TunerCfg]:
    return [c for c in cands
            if estimate_memory_bytes(c, tuner.n_params, tuner.hidden,
                                     tuner.layers, tuner.seq) < tuner.hbm]


# the propagation-backed static tuner rides alongside the calibrated
# analytic one: same package, program-derived costs (see static_tuner)
from .static_tuner import (MULTICHIP_VALIDATED, RankedConfig,  # noqa: E402
                           StaticAutoTuner, StaticConfig, estimate_cost,
                           pareto_front, rank_table,
                           top_is_pareto_consistent)

__all__ += ["StaticAutoTuner", "StaticConfig", "RankedConfig",
            "MULTICHIP_VALIDATED", "pareto_front",
            "top_is_pareto_consistent", "rank_table", "estimate_cost"]
