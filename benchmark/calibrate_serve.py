"""Readings a serving cell's limit is set from (PERF.md gives them).

    python3 benchmark/calibrate_serve.py --workload <cell> --seeds 12
                                         --control-seeds 3 --seconds 10

On the chip, at the cell's own size and load, a new process for each seed
(the engine's float32 build needs nearly the whole chip; the parent never
touches JAX). For each seed: a short window through the cell's own runner, and the widest gap by which a
served token lies below the plain reference's best (the lower readings).
For the first `--control-seeds` seeds also the control: the reference in
fp8 in the program's place, read at each position of the same prompts and
served tokens as the gap of the token fp8 puts first (the upper readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=2000)
    args = ap.parse_args(argv)
    if args.seeds > 1:
        import subprocess

        for i in range(args.seeds):
            subprocess.run([
                sys.executable, os.path.abspath(__file__), "--workload",
                args.workload, "--seeds", "1", "--control-seeds",
                str(int(i < args.control_seeds)), "--seconds",
                str(args.seconds), "--first-seed",
                str(args.first_seed + 7919 * i + (2 ** 31 if i % 2 else 0))],
                check=False)
        return 0
    import numpy as np

    from benchmark import compare, harness, weights
    from benchmark.runners import serve

    cell = harness.Cell.find(args.workload)
    harness.setup_compile_cache()
    devices = harness.require_tpu(cell.chips)
    config = cell.config
    ref = harness.load_module("reference", config["reference"])
    for i in range(args.seeds):
        seed = args.first_seed
        r = serve.run(cell, seed, args.seconds, False, devices,
                      time.perf_counter())
        row = {"seed": seed, "failed": r.failed,
               "program": r.compared["served_logit_gap"].value,
               "checked_tokens": r.stats["checked_tokens"]}
        if i < args.control_seeds:
            w = weights.make_like(r.stats["weight_shapes"], config, seed,
                                  donate=False)
            gap, std = 0.0, []
            for prompt, tokens in r.stats["sample"]:
                kw = dict(pad_to=r.stats["max_seq"])
                full = np.asarray(ref.logits_at(
                    w, prompt + tokens, len(prompt) - 1, config,
                    **kw))[:len(tokens)]
                low = np.asarray(ref.logits_at(
                    w, prompt + tokens, len(prompt) - 1, config,
                    precision="fp8", **kw))[:len(tokens)]
                gap = max(gap, compare.served_gap(full, low.argmax(-1)))
                std.append(float(full.std()))
            row["control_fp8"], row["logit_std"] = gap, float(np.mean(std))
            del w
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
