"""Readings a training cell's limits are set from (PERF.md gives them).

    python3 benchmark/calibrate_train.py --workload <cell> --seeds 12
                                         --control-seeds 3

One process on the chip, at the cell's own size. For each seed: the
program's first steps against the plain reference (the lower readings).
For the first `--control-seeds` seeds also the reference put in the
program's place in fp8 (the control) and with half of the batch left out
(a planted fault), each against the float32 reference (the upper
readings). One JSON line a seed; nothing is timed.
"""
from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    from benchmark import compare, harness, traffic_gen, weights
    from benchmark.runners import train

    cell = harness.Cell.find(args.workload)
    harness.setup_compile_cache()
    devices = harness.require_tpu(cell.chips)
    config, opt = cell.config, cell.config["optimizer"]
    ref = harness.load_module("reference", config["reference"])
    def gaps(readings, reference):
        """Every reading, held or not, with the leaf that reads worst."""
        every = {k: 0.0 for k in ("loss1_rel", "loss2_rel", "loss3_rel",
                                  "grad_norm_gap", "change_norm_gap")}
        compared, _ = compare.train_readings(readings, reference, every)
        return {k: [c.value, c.where] if c.where else c.value
                for k, c in compared.items()}

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i + (2 ** 31 if i % 2 else 0)
        batches = traffic_gen.train_batches(cell.traffic, seed,
                                            config["vocab_size"])
        trainer = train.build_trainer(config, devices, seed)
        trainer.params = weights.make_train_params(
            config, seed, trainer.param_shardings)
        program = train.first_steps(trainer, batches, config, seed)
        del trainer
        gc.collect()
        t0 = time.perf_counter()
        reference = ref.first_steps(weights.make_train_params(config, seed),
                                    batches[:3], config, opt)
        row = {"seed": seed, "reference_s": time.perf_counter() - t0,
               "loss": reference["loss"], "program": gaps(program, reference)}
        if i < args.control_seeds:
            half = cell.traffic["batch"] // 2
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch", {"rows": half})):
                other = ref.first_steps(
                    weights.make_train_params(config, seed), batches[:3],
                    config, opt, **kw)
                row[name] = gaps(other, reference)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
