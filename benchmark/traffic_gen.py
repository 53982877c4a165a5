"""The one traffic generator. A mix is a data file under `traffic/`.

Two kinds of mix, told apart by `kind`:

- `train_batches`: `distinct_batches` host batches of `batch` x `seq` token
  ids, drawn from the seed, cycled by the runner; the labels are the ids
  shifted by one. All rows differ.
- `open_loop`: requests on a schedule that does not wait for replies.
  Lengths and gaps are FIXED sets, the stratified quantiles of the mix's
  distributions, in one fixed order that the seed only turns like a
  ring: every seed offers the same work and the same bursts, begun at
  another place, so that runs differ by the system and not by how much
  was asked or how it bunched.

Nothing here knows a cell, a model or the program.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def train_batches(mix: dict, seed: int, vocab: int):
    """[(ids, labels)] int32 arrays [batch, seq]."""
    if mix["kind"] != "train_batches":
        raise ValueError(f"not a train mix: {mix['kind']!r}")
    if mix["token_ids"] != "uniform":
        raise ValueError(f"unknown token_ids {mix['token_ids']!r}")
    rng = _rng(seed, 0)
    out = []
    for _ in range(mix["distinct_batches"]):
        ids = rng.integers(0, vocab, (mix["batch"], mix["seq"]),
                           dtype=np.int32)
        out.append((ids, np.roll(ids, -1, axis=1)))
    return out


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified quantiles of `dist`, as whole numbers where clipped to
    [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]))
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def _gaps(arrivals: str, rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    if arrivals == "poisson":
        return -np.log1p(-q) / rate
    if arrivals == "uniform":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrivals {arrivals!r}")


@dataclass
class Request:
    due_s: float                # from the start of the schedule
    prompt: List[int]
    max_new: int
    phase: int                  # index into the phases it was drawn for


def open_loop(mix: dict, seed: int, vocab: int, phases: List[float]):
    """Requests by due time over consecutive phases of the given lengths
    in seconds (the runner's: ramp, window, tail). Each phase holds
    round(rate x length) requests with its OWN fixed sets of lengths and
    gaps, the gaps scaled to fill the phase exactly and the first arrival
    at its start. The sets stand in ONE order (the mix's `order_seed`),
    a ring that the run's seed only turns: every seed's window holds the
    same requests with the same neighbours, begun at another place. (With
    the order itself drawn from the seed, the first token's 95th
    percentile spread by 6-18% over seeds and the tokens a second by 6%,
    while two runs of one seed agreed to 1%: my chip runs, PR 24.)"""
    if mix["kind"] != "open_loop":
        raise ValueError(f"not an open-loop mix: {mix['kind']!r}")
    rate = float(mix["rate_per_s"])
    order = _rng(mix.get("order_seed", 0), 1)      # one order for all seeds
    turn, ids = _rng(seed, 1), _rng(seed, 2)
    shared = int(mix.get("shared_prefix_len", 0))
    groups = int(mix.get("shared_prefix_groups", 1))
    prefixes = [ids.integers(1, vocab, shared).tolist()
                for _ in range(groups if shared else 0)]
    out, start = [], 0.0
    for phase, seconds in enumerate(phases):
        n = int(round(rate * seconds))
        if n > 0:
            k = int(turn.integers(0, n))
            gaps = _gaps(mix["arrivals"], rate, n)
            gaps, prompts, outputs = (
                np.roll(order.permutation(x), k) for x in (
                    gaps * (seconds / gaps.sum()),
                    _quantiles(mix["prompt_len"], n),
                    _quantiles(mix["output_len"], n)))
            due = start + np.cumsum(gaps) - gaps
            for i in range(n):
                body = ids.integers(1, vocab, int(prompts[i])).tolist()
                if shared:
                    body = (prefixes[len(out) % groups] + body)[
                        :max(int(prompts[i]), shared + 1)]
                out.append(Request(float(due[i]), body, int(outputs[i]),
                                   phase))
        start += seconds
    return out
